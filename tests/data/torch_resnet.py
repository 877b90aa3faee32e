"""Plain-torch ResNet-18 for ONNX-import parity tests and the import
bench (the image has no torchvision; this is the standard BasicBlock
architecture written directly — conv3x3/BN/ReLU pairs with identity or
1x1-projection shortcuts, the graph ImageFeaturizer.scala:40-215 scores
through its downloaded CNTK model zoo).

Weights are seeded-random (eval-mode BN uses the seeded running stats):
the parity target is torch's own forward on the same weights, so nothing
pretrained is needed and the ~45 MB fixture never has to be committed —
callers export to a temp file via `export_resnet18_onnx`.
"""
import numpy as np
import torch
import torch.nn as nn


class BasicBlock(nn.Module):
    def __init__(self, cin, cout, stride=1):
        super().__init__()
        self.conv1 = nn.Conv2d(cin, cout, 3, stride=stride, padding=1,
                               bias=False)
        self.bn1 = nn.BatchNorm2d(cout)
        self.conv2 = nn.Conv2d(cout, cout, 3, padding=1, bias=False)
        self.bn2 = nn.BatchNorm2d(cout)
        self.relu = nn.ReLU()
        if stride != 1 or cin != cout:
            self.down = nn.Sequential(
                nn.Conv2d(cin, cout, 1, stride=stride, bias=False),
                nn.BatchNorm2d(cout))
        else:
            self.down = None

    def forward(self, x):
        idt = x if self.down is None else self.down(x)
        y = self.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return self.relu(y + idt)


class ResNet18(nn.Module):
    def __init__(self, num_classes=1000):
        super().__init__()
        self.stem = nn.Sequential(
            nn.Conv2d(3, 64, 7, stride=2, padding=3, bias=False),
            nn.BatchNorm2d(64), nn.ReLU(),
            nn.MaxPool2d(3, stride=2, padding=1))
        layers = []
        cin = 64
        for cout, stride in ((64, 1), (64, 1), (128, 2), (128, 1),
                             (256, 2), (256, 1), (512, 2), (512, 1)):
            layers.append(BasicBlock(cin, cout, stride))
            cin = cout
        self.blocks = nn.Sequential(*layers)
        self.gap = nn.AdaptiveAvgPool2d(1)
        self.flatten = nn.Flatten()
        self.fc = nn.Linear(512, num_classes)

    def forward(self, x):
        return self.fc(self.flatten(self.gap(self.blocks(self.stem(x)))))


def make_resnet18(seed: int = 0, num_classes: int = 1000) -> ResNet18:
    torch.manual_seed(seed)
    m = ResNet18(num_classes)
    # randomized running stats so eval-mode BN is a real affine transform
    # (fresh stats are mean=0/var=1, which folds to near-identity and
    # would under-test the BatchNormalization import path)
    g = torch.Generator().manual_seed(seed + 1)
    with torch.no_grad():
        for mod in m.modules():
            if isinstance(mod, nn.BatchNorm2d):
                mod.running_mean.copy_(
                    torch.randn(mod.num_features, generator=g) * 0.1)
                mod.running_var.copy_(
                    torch.rand(mod.num_features, generator=g) * 0.5 + 0.75)
    m.eval()
    return m


# Known homes of the exporter's post-export onnxscript merge across torch
# releases (a PRIVATE internal — it moves): probed in order.
_ONNXSCRIPT_MERGE_PATHS = (
    "torch.onnx._internal.torchscript_exporter.onnx_proto_utils",
    "torch.onnx._internal.onnx_proto_utils",
)


def _find_onnx_proto_utils():
    import importlib
    for mod_path in _ONNXSCRIPT_MERGE_PATHS:
        try:
            mod = importlib.import_module(mod_path)
        except Exception:  # noqa: BLE001 - private path absent in this torch
            continue
        if hasattr(mod, "_add_onnxscript_fn"):
            return mod
    return None


def export_resnet18_onnx(path: str, seed: int = 0, spatial: int = 224,
                         num_classes: int = 1000):
    """Export a seeded ResNet-18 to `path`; returns (model, example_input,
    example_output) for parity checks. Temporarily patches the torch
    exporter's post-export onnxscript merge like make_onnx_fixtures.py (the
    image has no `onnx` package and these graphs have no custom ops) — the
    patch is scoped to the export and RESTORED after, since the target is a
    process-global torch private. When the private path has moved in this
    torch build: a clear pytest skip inside a test run, a plain
    RuntimeError from CLI callers (which must not grow a pytest
    dependency)."""
    import os
    mod = _find_onnx_proto_utils()
    if mod is None:
        msg = ("torch.onnx internals moved: no _add_onnxscript_fn under any "
               f"of {_ONNXSCRIPT_MERGE_PATHS}; update _ONNXSCRIPT_MERGE_PATHS "
               "for this torch version")
        if os.environ.get("PYTEST_CURRENT_TEST"):
            import pytest
            pytest.skip(msg)
        raise RuntimeError(msg)
    original = mod._add_onnxscript_fn
    mod._add_onnxscript_fn = lambda model_bytes, _: model_bytes
    try:
        model = make_resnet18(seed, num_classes)
        x = torch.randn(2, 3, spatial, spatial,
                        generator=torch.Generator().manual_seed(seed + 2))
        torch.onnx.export(model, x, path, opset_version=13, dynamo=False)
    finally:
        mod._add_onnxscript_fn = original
    with torch.no_grad():
        y = model(x)
    return model, x.numpy(), y.numpy()
