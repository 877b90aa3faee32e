"""The gated delta rule's Pallas kernels (`ops/gated_delta.py`: `gdn_fwd`,
`gdn_bwd`) through the interpreter on the CPU: output and all five gradients
against the benchmark's positional reference and against the XLA form, the
shape rule that chooses between kernel and XLA form, the counters that say
which was taken, and the kernels' place in the compiled step's regions; and,
because one file a process may describe the chip in, the same compiled
checks for the mixer's own kernels (`ops/gdn_mixer.py`, whose parities are
in test_gdn_mixer_kernels.py) and for the expert layer's
(`ops/moe_experts.py`, parities in test_moe_kernel.py)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import gated_delta as gd
from mmlspark_tpu.ops import gdn_mixer as gm
from mmlspark_tpu.ops import moe_experts as me
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "reference_qwen3_next",
        os.path.join(REPO, "benchmark", "reference", "qwen3_next.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(seq, heads, key_heads, dtype, batch=2, dk=128, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 6)

    def l2(t):
        return t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    args = ((l2(jax.random.normal(ks[0], (batch, seq, key_heads, dk)))
             * dk ** -0.5).astype(dtype),
            l2(jax.random.normal(ks[1], (batch, seq, key_heads, dk))
               ).astype(dtype),
            jax.random.normal(ks[2], (batch, seq, heads, dv)).astype(dtype),
            -jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
            * 0.3,
            jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads))))
    return args, jax.random.normal(ks[5], (batch, seq, heads, dv))


def out_and_grads(fn, args, cot):
    def loss(*a):
        o = fn(*a)
        return (o.astype(jnp.float32) * cot).sum(), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return (o,) + grads


def worst(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# (value heads, key heads, heads a step): one head block and more than one;
# key heads repeated to the value heads inside the kernel or not at all
LAYOUTS = [(2, 2, 2), (4, 2, 2), (4, 4, 4)]


@pytest.mark.parametrize("seq", [64, 150, 257])
@pytest.mark.parametrize("heads,key_heads,step", LAYOUTS)
def test_float32_kernels_match_reference_and_xla_form(ref, seq, heads,
                                                      key_heads, step):
    args, cot = inputs(seq, heads, key_heads, jnp.float32)
    rep = heads // key_heads

    def positional(q, k, v, g, beta):
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
        return jax.vmap(ref.delta_rule)(q, k, v, g, beta)

    with jax.default_matmul_precision("highest"):
        got = out_and_grads(lambda *a: gd.gated_delta_pallas(
            *a, interpret=True, heads_per_step=step), args, cot)
        xla = out_and_grads(gd.chunk_gated_delta_rule, args, cot)
        want = out_and_grads(positional, args, cot)
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-6
    assert float(jnp.abs(got[0] - xla[0]).max()) < 2e-6
    for a, b, c in zip(got[1:], want[1:], xla[1:]):
        assert worst(a, b) < 2e-5
        assert worst(a, c) < 2e-5


@pytest.mark.parametrize("seq", [64, 150, 257])
@pytest.mark.parametrize("heads,key_heads,step", LAYOUTS[:2])
def test_bfloat16_kernels_match_the_xla_form(seq, heads, key_heads, step):
    """Against the XLA form in bfloat16, each within what bfloat16 operands
    leave of the float32 XLA form: the kernels round no more than it."""
    args, cot = inputs(seq, heads, key_heads, jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    got = out_and_grads(lambda *a: gd.gated_delta_pallas(
        *a, interpret=True, heads_per_step=step), args, cot)
    xla = out_and_grads(gd.chunk_gated_delta_rule, args, cot)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(gd.chunk_gated_delta_rule, exact, cot)
    assert got[0].dtype == jnp.bfloat16
    for a, b, c in zip(got, xla, want):
        assert a.dtype == b.dtype
        assert worst(a, b) < 0.03
        assert worst(a, c) < max(1.5 * worst(b, c), 0.01)


def test_unbatched_call_is_the_batched_one():
    args, _ = inputs(100, 2, 2, jnp.float32, batch=1)
    one = gd.gated_delta_pallas(*(a[0] for a in args), interpret=True)
    assert float(jnp.abs(one - gd.gated_delta_pallas(
        *args, interpret=True)[0]).max()) == 0.0


SCAN_ROUTES = (tnames.GDN_SCAN_ROUTE_PALLAS, tnames.GDN_SCAN_ROUTE_XLA)
MIXER_ROUTES = (tnames.GDN_MIXER_ROUTE_PALLAS, tnames.GDN_MIXER_ROUTE_XLA)


def routes(fn, names=SCAN_ROUTES):
    before = [reliability_metrics.get(n) for n in names]
    fn()
    return tuple(reliability_metrics.get(n) - b
                 for n, b in zip(names, before))


@pytest.mark.parametrize("dk,dv,chunk,dtype", [
    (16, 24, 64, jnp.float32),        # the toy widths of the trainer's tests
    (128, 128, 16, jnp.float32),      # another chunk than the kernels'
    (128, 128, 64, jnp.float16),      # a dtype they were not written for
    (128, 128, 64, jnp.float32),      # fits, but this is not a TPU
])
def test_what_does_not_fit_takes_the_xla_form_and_is_counted(dk, dv, chunk,
                                                             dtype):
    args, _ = inputs(40, 2, 2, dtype, batch=1, dk=dk, dv=dv)
    fits = dk == dv == 128 and chunk == 64 and dtype == jnp.float32
    assert gd.pallas_fits(args[0], args[2], chunk) == fits
    assert routes(lambda: gd.chunk_gated_delta_rule(
        *args, chunk=chunk)) == (0, 1)


def test_interpret_call_takes_the_kernels_and_is_counted():
    args, _ = inputs(40, 2, 2, jnp.float32, batch=1)
    assert routes(lambda: gd.gated_delta_pallas(
        *args, interpret=True)) == (1, 0)
    bad, _ = inputs(40, 2, 2, jnp.float32, batch=1, dk=16)
    with pytest.raises(ValueError, match="multiples of 128"):
        gd.gated_delta_pallas(*bad, interpret=True)


# ------------------------------------------- compiled for a described v5e
# Only one process at a time may load the TPU's library, so the topology is
# described inside a fixture of this one file (never at import), and every
# test below compiles in this process.

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo, SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_kernels_compile_for_v5e_at_the_published_widths(v5e):
    """32 value heads over 16 key heads of 128, bfloat16, as the cell runs
    them (a shorter sequence: the grid's length is no part of a kernel):
    what Mosaic refuses (an unaligned slice, too much VMEM) it refuses
    here."""
    _, chip = v5e

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    args = (shape(2, 512, 16, 128), shape(2, 512, 16, 128),
            shape(2, 512, 32, 128), shape(2, 512, 32, dtype=jnp.float32),
            shape(2, 512, 32, dtype=jnp.float32))
    text = jax.jit(jax.grad(
        lambda *a: gd.gated_delta_pallas(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert gd.KERNEL_FWD in text and gd.KERNEL_BWD in text
    plain = jax.jit(gd.gated_delta_pallas).lower(*args).compile().as_text()
    assert f"%{gd.KERNEL_FWD}." in plain and gd.KERNEL_BWD not in plain


LAYER_TOKENS = (2, 512)


@pytest.fixture(scope="module")
def gdn_layer(v5e):
    """A DeltaNet layer as the trainer runs it (`jax.checkpoint` round the
    mixer, bfloat16), at head sizes that fit the kernels, its gradient
    compiled for the chip: (the optimized text, its scope map, what the
    recurrence's and the mixer's route counters counted meanwhile)."""
    from mmlspark_tpu.models.dnn import hybrid_layers
    from mmlspark_tpu.models.dnn.lm_spec import qwen3_next_spec
    from mmlspark_tpu.telemetry import perf
    topo, chip = v5e
    cfg = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
               head_dim=32, num_attention_heads=4, num_key_value_heads=2,
               partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=128, linear_value_head_dim=128,
               linear_conv_kernel_dim=4, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               norm_topk_prob=True, vocab_size=97)
    spec = qwen3_next_spec(cfg, (0, 8))
    layer = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.ShapeDtypeStruct(
            a.shape[1:], jnp.float32 if path[-1].key in
            hybrid_layers.F32_LEAVES else jnp.bfloat16, sharding=chip),
        hybrid_layers.init_hybrid(spec, 0)["layers"][0])
    h = jax.ShapeDtypeStruct(LAYER_TOKENS + (64,), jnp.bfloat16,
                             sharding=chip)

    def loss(h, lp):
        out, _ = hybrid_layers.hybrid_layer(h, lp, "gdn", spec, "dense",
                                            remat=True)
        return out.astype(jnp.float32).sum()

    # the choice of path asks the platform of jax.devices()[0]
    real_devices = jax.devices
    jax.devices = lambda *a: topo.devices
    text, counted = [], {}
    try:
        counted["scan"] = routes(lambda: counted.update(mixer=routes(
            lambda: text.append(jax.jit(jax.grad(loss, argnums=(
                0, 1))).lower(h, layer).compile().as_text()),
            MIXER_ROUTES)))
    finally:
        jax.devices = real_devices
    return text[0], perf.scope_map(text[0]), counted



def kernel_ways(scopes, *kernels):
    """{(region, way)} of the instructions named after `kernels`."""
    return {where for name, where in scopes.items()
            if name.split(".")[0] in kernels}


def test_compiled_layer_holds_the_kernels_in_the_scan_region(gdn_layer):
    """Every call of the recurrence's kernels lies in region `lm.gdn.scan`,
    `gdn_fwd` as `fwd` and as `remat`, `gdn_bwd` as `bwd`, so
    `gdn_scan_ms_per_step` reads all of the recurrence; and the choice was
    counted."""
    _, scopes, counted = gdn_layer
    assert counted["scan"][0] > 0 and counted["scan"][1] == 0
    assert not [name for name in scopes if name.startswith("vmap_gdn_")]
    assert kernel_ways(scopes, gd.KERNEL_FWD) == {
        (tnames.LM_GDN_SCAN, "fwd"), (tnames.LM_GDN_SCAN, "remat")}
    assert kernel_ways(scopes, gd.KERNEL_BWD) == {(tnames.LM_GDN_SCAN, "bwd")}


# ---- the mixer's fused passes round the recurrence (ops/gdn_mixer.py)

def test_mixer_kernels_compile_for_v5e_at_the_published_widths(v5e):
    """16 key heads under 32 value heads of 128, bfloat16, the blocks the
    cell runs (a shorter sequence: the grid's length is no part of a
    kernel): what Mosaic refuses it refuses here."""
    _, chip = v5e

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    heads = (16, 128, 32, 128)

    def prep_loss(qkv, taps):
        return sum(t.astype(jnp.float32).sum()
                   for t in gm.prepare_pallas(qkv, taps, heads))

    def post_loss(o, z, w):
        return gm.finish_pallas(o, z, w, 128, 1e-6).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(prep_loss, argnums=(0, 1))).lower(
        shape(2, 512, 8192), shape(4, 8192, dtype=jnp.float32)
    ).compile().as_text()
    # one call each for q, k and v, forward and backward
    assert text.count(f"%{gm.KERNEL_PREP_FWD}.") >= 3
    assert text.count(f"%{gm.KERNEL_PREP_BWD}.") >= 3
    text = jax.jit(jax.value_and_grad(post_loss, argnums=(0, 1, 2))).lower(
        shape(2, 512, 4096), shape(2, 512, 4096),
        shape(128, dtype=jnp.float32)).compile().as_text()
    assert f"%{gm.KERNEL_POST_FWD}." in text
    assert f"%{gm.KERNEL_POST_BWD}." in text


def test_compiled_layer_keeps_the_slab_and_the_regions(gdn_layer):
    """The mixer's four kernels lie in region `lm.gdn` outside
    `lm.gdn.scan`, the forward ones as `fwd` and as `remat`, the backward
    ones as `bwd`; and between the projections nothing of a slab's size is
    copied, reshaped or transposed."""
    import re
    text, scopes, counted = gdn_layer
    assert counted["mixer"][0] > 0 and counted["mixer"][1] == 0
    assert kernel_ways(scopes, gm.KERNEL_PREP_FWD, gm.KERNEL_POST_FWD) == {
        (tnames.LM_GDN, "fwd"), (tnames.LM_GDN, "remat")}
    assert kernel_ways(scopes, gm.KERNEL_PREP_BWD, gm.KERNEL_POST_BWD) == {
        (tnames.LM_GDN, "bwd")}
    # the narrowest slab here is q's, (2, 512, 2 x 128)
    slab = int(np.prod(LAYER_TOKENS)) * 2 * 128
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%((?:copy|reshape|transpose)[\w.-]*) = "
                     r"\w+\[([\d,]*)\]", line)
        if m and scopes.get(m.group(1), ("",))[0] in (
                tnames.LM_GDN, tnames.LM_GDN_SCAN) and np.prod(
                [int(n) for n in m.group(2).split(",") if n]) >= slab:
            moved.append(m.group(1))
    assert moved == []


# ---- the expert layer's tile loop as kernels (ops/moe_experts.py)

MOE_ROUTES = (tnames.MOE_EXPERTS_ROUTE_PALLAS, tnames.MOE_EXPERTS_ROUTE_XLA)
# (model width, expert width, experts a token) as the two share cells run
# them; fewer tokens and held experts (the grid's length and the number of
# weight blocks are no part of a kernel)
MOE_WIDTHS = {"qwen3-next": (2048, 512, 10), "lfm2": (2048, 1536, 4)}


@pytest.mark.parametrize("model", sorted(MOE_WIDTHS))
def test_expert_kernels_compile_for_v5e_at_the_published_widths(v5e, model):
    """bfloat16, tiles of `moe.TILE` rows: the per-row copies, the resident
    weight and gradient blocks and the VMEM they take are Mosaic's to
    refuse here (LFM2's backward walks its width in two blocks)."""
    from mmlspark_tpu.models.dnn import moe
    _, chip = v5e
    d, f, k = MOE_WIDTHS[model]
    n, held = 1024, 4

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    assert me.pallas_fits(shape(n, d), shape(held, d, f), k, moe.TILE)

    def loss(idx, x, top_p, wg, wu, wd):
        plan = moe.dispatch_plan(idx, 0, held, top_p)
        return me.experts_pallas(x, top_p, wg, wu, wd,
                                 plan).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5))).lower(
        shape(n, k, dtype=jnp.int32), shape(n, d),
        shape(n, k, dtype=jnp.float32), shape(held, d, f),
        shape(held, d, f), shape(held, f, d)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert f"%{me.KERNEL_FWD}." in text and f"%{me.KERNEL_BWD}." in text


def test_checkpointed_expert_sublayer_runs_each_kernel_once(v5e):
    """An expert sublayer as the share families run it (`remat` true: a
    checkpoint that keeps the routing), compiled for the chip: ONE
    `moe_fwd` and ONE `moe_bwd` (the backward reads the layer's inputs and
    the kept plan, so nothing runs the forward kernel again), both in
    region `lm.moe.experts`, and the choice was counted."""
    import re
    from mmlspark_tpu.models.dnn import hybrid_layers, moe
    from mmlspark_tpu.telemetry import perf
    topo, chip = v5e
    n, d, f, k, held, e_all = 1024, 256, 128, 2, 4, 8

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=chip)

    lp = {"router": shape(d, e_all), "w_gate": shape(held, d, f),
          "w_up": shape(held, d, f), "w_down": shape(held, f, d)}

    def feed(h, lp):
        out, stats = moe.moe_layer(h, lp, k, (0, held))
        return h + out, stats

    _, feed = hybrid_layers.checkpoint_sublayers(
        lambda h, lp: h, feed, True, flash=False, routing=True)

    def loss(h, lp):
        return feed(h, lp)[0].astype(jnp.float32).sum()

    # the choice of path asks the platform of jax.devices()[0]
    real_devices = jax.devices
    jax.devices = lambda *a: topo.devices
    text = []
    try:
        counted = routes(lambda: text.append(jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1))).lower(shape(n, d), lp).compile(
            ).as_text()), MOE_ROUTES)
    finally:
        jax.devices = real_devices
    assert counted == (1, 0)
    for kernel in (me.KERNEL_FWD, me.KERNEL_BWD):
        assert len(re.findall(rf"%{kernel}\.\d+ = ", text[0])) == 1, kernel
    scopes = perf.scope_map(text[0])
    assert kernel_ways(scopes, me.KERNEL_FWD) == {
        (tnames.LM_MOE_EXPERTS, "fwd")}
    assert kernel_ways(scopes, me.KERNEL_BWD) == {
        (tnames.LM_MOE_EXPERTS, "bwd")}
