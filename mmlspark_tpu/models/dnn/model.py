"""DNNModel: jitted minibatch deep-net inference over Table columns.

Role-equivalent to CNTKModel (reference: cntk/CNTKModel.scala:87-543):
the reference broadcasts protobuf model bytes once, clones per partition
with shared parameters, builds native Values per minibatch, and evaluates
on the default device. TPU-native redesign:

- the "graph" is a jittable apply(params, batch) function + a params
  pytree; compile-once replaces clone-per-partition (the XLA executable IS
  the shared immutable model);
- minibatching pads every batch to a STATIC shape so one executable serves
  all batches (ragged last batch padded, rows masked off afterwards) —
  no recompiles, no dynamic shapes;
- feed/fetch dicts map Table columns to model inputs/outputs
  (CNTKModel.scala:207-226 feedDict/fetchDict sugar);
- serialization: params round-trip as arrays; the traced function round-trips
  as a StableHLO artifact via jax.export when `export_bytes` is used —
  the moral equivalent of CNTK's protobuf-bytes SerializableFunction
  (com/microsoft/CNTK/SerializableFunction.scala:25-45).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import numpy as np

from ...core import Model, Param, Table
from ...core.params import in_range


class DNNModel(Model):
    """Transformer scoring Table columns through a jitted network."""
    input_col = Param("input_col", "input column (feeds the model)", "features")
    output_col = Param("output_col", "output column", "scores")
    batch_size = Param("batch_size", "minibatch rows per dispatch", 64,
                       validator=in_range(1))
    output_index = Param("output_index",
                         "when apply returns a tuple/list/dict: which output "
                         "to emit", None)
    input_dtype = Param("input_dtype", "cast input batches to this dtype",
                        "float32")

    def __init__(self, apply_fn: Optional[Callable] = None, params=None, **kw):
        super().__init__(**kw)
        self._apply_fn = apply_fn
        self._params = params
        self._jitted = None
        self._export_bytes: Optional[bytes] = None

    # -- persistence --------------------------------------------------------
    def _get_state(self):
        import jax
        state = {}
        if self._params is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._params)
            state["treedef"] = _treedef_to_str(self._params)
            for i, leaf in enumerate(leaves):
                state[f"leaf_{i}"] = np.asarray(leaf)
            state["n_leaves"] = len(leaves)
        if self._export_bytes is None and self._apply_fn is not None:
            try:
                self._export_bytes = self.export_stablehlo()
            except Exception:  # noqa: BLE001 - fn may not be exportable (closure over py state)
                pass
        if self._export_bytes is not None:
            state["stablehlo"] = np.frombuffer(self._export_bytes, np.uint8)
        return state

    def _set_state(self, s):
        import jax
        n = int(np.asarray(s.get("n_leaves", 0)))
        if n:
            leaves = [np.asarray(s[f"leaf_{i}"]) for i in range(n)]
            self._params = _treedef_from_str(str(s["treedef"]), leaves)
        if "stablehlo" in s:
            self._export_bytes = np.asarray(s["stablehlo"], np.uint8).tobytes()
            exported = jax.export.deserialize(bytearray(self._export_bytes))
            self._apply_fn = None
            self._exported_call = exported.call
            self._jitted = None

    # -- StableHLO round-trip (CNTK protobuf-bytes equivalent) ---------------
    def export_stablehlo(self) -> bytes:
        """Serialize (apply_fn, params, batch shape) as a portable StableHLO
        artifact (jax.export) — the deep-net graph as bytes, like the
        reference ships CNTK protobufs."""
        import jax
        import jax.numpy as jnp
        if self._apply_fn is None:
            raise ValueError("no apply_fn to export")
        shape = self._example_shape
        spec = jax.ShapeDtypeStruct((self.batch_size, *shape),
                                    jnp.dtype(self.input_dtype))
        fn = functools.partial(self._apply_fn, self._params)
        exported = jax.export.export(jax.jit(fn))(spec)
        return exported.serialize()

    # -- scoring ------------------------------------------------------------
    @property
    def _example_shape(self):
        if not hasattr(self, "_row_shape"):
            raise ValueError("transform once (or set _row_shape) before export")
        return self._row_shape

    def _compiled(self):
        import jax
        if self._jitted is None:
            if self._apply_fn is not None:
                fn = self._apply_fn
                params = self._params
                self._jitted = jax.jit(lambda xb: fn(params, xb))
            elif getattr(self, "_exported_call", None) is not None:
                self._jitted = self._exported_call
            else:
                raise ValueError("DNNModel has neither apply_fn nor a "
                                 "deserialized StableHLO graph")
        return self._jitted

    def _transform(self, t: Table) -> Table:
        import jax
        x = np.asarray(t[self.input_col])
        n = x.shape[0]
        self._row_shape = tuple(x.shape[1:])
        b = self.batch_size
        fn = self._compiled()
        outs = []
        for lo in range(0, n, b):
            xb = x[lo:lo + b].astype(self.input_dtype)
            pad = b - xb.shape[0]
            if pad:  # static batch shape: one executable for every batch
                xb = np.pad(xb, ((0, pad),) + ((0, 0),) * (xb.ndim - 1))
            res = fn(xb)
            res = self._select_output(res)
            outs.append(np.asarray(res)[:b - pad])
        scores = np.concatenate(outs) if outs else np.zeros((0,))
        return t.with_column(self.output_col, scores)

    def _select_output(self, res):
        if self.output_index is None:
            return res
        if isinstance(res, dict):
            return res[self.output_index]
        return res[int(self.output_index)]


def _treedef_to_str(tree) -> str:
    """Portable treedef description (dict/list/tuple nesting only)."""
    import jax
    import json

    def describe(t):
        if isinstance(t, dict):
            return {"d": {k: describe(v) for k, v in sorted(t.items())}}
        if isinstance(t, (list, tuple)):
            return {"l": [describe(v) for v in t]}
        return "leaf"

    return json.dumps(describe(tree))


def _treedef_from_str(s: str, leaves: list):
    import json
    it = iter(leaves)

    def build(d):
        if d == "leaf":
            return next(it)
        if "d" in d:
            return {k: build(v) for k, v in d["d"].items()}
        return [build(v) for v in d["l"]]

    return build(json.loads(s))


def tree_to_payload(tree, prefix: str, leaves_only: bool = False) -> dict:
    """Flatten a param tree into numbered payload keys for the checkpoint /
    state stores: {prefix}_{i} arrays + n_{prefix} count (+ treedef_{prefix}
    unless leaves_only — optax NamedTuple nodes don't round-trip through
    the treedef string, so optimizer states save leaves only)."""
    import jax
    import numpy as np
    leaves, _ = jax.tree_util.tree_flatten(tree)
    out = {f"n_{prefix}": len(leaves)}
    if not leaves_only:
        out[f"treedef_{prefix}"] = _treedef_to_str(tree)
    for i, leaf in enumerate(leaves):
        out[f"{prefix}_{i}"] = np.asarray(leaf)
    return out


def tree_from_payload(payload: dict, prefix: str, leaves_only: bool = False):
    """Inverse of tree_to_payload: the rebuilt tree, or (leaves_only) the
    flat leaf list for the caller to pour into a live structure."""
    import numpy as np
    n = int(np.asarray(payload[f"n_{prefix}"]))
    leaves = [np.asarray(payload[f"{prefix}_{i}"]) for i in range(n)]
    if leaves_only:
        return leaves
    return _treedef_from_str(str(payload[f"treedef_{prefix}"]), leaves)
