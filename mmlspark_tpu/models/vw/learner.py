"""Hashed-feature online learning as fused XLA programs.

Role-equivalent to the VW C++ core the reference drives over JNI
(vw/VowpalWabbitBase.scala:338-424): per-example SGD over a 2^b weight
vector with plain / adaptive (AdaGrad) / BFGS modes, multiple passes, and
per-pass cross-worker weight averaging (the native spanning-tree AllReduce,
VowpalWabbitBase.scala:434-460 — here a `lax.pmean` over the mesh's data
axis inside shard_map).

TPU-first divergence (documented): VW updates weights per example; a strict
serial chain cannot use the VPU/MXU. Training here is MINIBATCH SGD — one
fused lax.scan over batches per pass, weight gradients via segment_sum over
hashed indices. With batch_size=1 the reference's semantics are recovered
exactly (at serial speed); default 256 matches VW quality on the reference's
regression suites within its own golden tolerance (±1.0 loss).

The learning-rate schedule mirrors VW: lr_t = lr * (t0 / (t0 + t))^power_t
with power_t=0.5, applied per batch; adaptive mode uses AdaGrad
accumulators like --adaptive.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class VWParams:
    num_bits: int = 18
    loss_function: str = "squared"   # squared | logistic
    learning_rate: float = 0.5       # VW default
    power_t: float = 0.5
    initial_t: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    num_passes: int = 1
    batch_size: int = 256
    mode: str = "adaptive"           # adaptive (VW default) | sgd | bfgs
    bfgs_iters: int = 25
    bfgs_memory: int = 10
    seed: int = 0


def _pad_batches(idx, val, y, w, batch_size):
    n = idx.shape[0]
    nb = max(1, -(-n // batch_size))
    pad = nb * batch_size - n
    if pad:
        idx = np.pad(idx, ((0, pad), (0, 0)))
        val = np.pad(val, ((0, pad), (0, 0)))      # value 0 -> no gradient
        y = np.pad(y, (0, pad))
        w = np.pad(w, (0, pad))                    # weight 0 -> no loss
    return (idx.reshape(nb, batch_size, -1), val.reshape(nb, batch_size, -1),
            y.reshape(nb, batch_size), w.reshape(nb, batch_size), nb)


def _predict_margin(weights, bias, idx, val):
    # gather from the 2^b table; k is small (feature count), rows vectorize.
    # indices are masked into the table like VW masks every hash (the
    # feature space is DEFINED modulo 2^b, so out-of-range producers such as
    # a Featurize layout wider than the table wrap instead of clamping)
    idx = idx & (weights.shape[0] - 1)
    return jnp.sum(weights[idx] * val, axis=-1) + bias


@functools.partial(jax.jit, static_argnames=("link",))
def _predict_sparse(weights, bias, idx, val, link=None):
    """Compiled sparse-pair scoring — the serving fast path's kernel.

    Shape-bucketed by the caller (ServingTransform pads rows and pairs
    to power-of-two buckets), so jit's cache holds one executable per
    (rows, k) bucket and `plan.recompiles` stays 0."""
    m = _predict_margin(weights, bias, idx, val)
    if link == "logistic":
        m = jax.nn.sigmoid(m)
    return m


def _loss_grad(margin, y, w, loss_function: str):
    if loss_function == "logistic":
        # y in {0,1}; VW reports logistic loss
        p = jax.nn.sigmoid(margin)
        grad = (p - y) * w
        loss = -(y * jnp.log(jnp.clip(p, 1e-15, 1.0))
                 + (1 - y) * jnp.log(jnp.clip(1 - p, 1e-15, 1.0))) * w
    else:
        d = margin - y
        grad = d * w
        loss = 0.5 * d * d * w
    return grad, loss


@functools.partial(jax.jit,
                   static_argnames=("p", "nb", "axis_name"))
def _fit_sgd(b_idx, b_val, b_y, b_w, p: VWParams, nb: int,
             init_w, init_b, axis_name: Optional[str] = None):
    """All passes fused: scan over passes, inner scan over minibatches.
    Per-pass pmean over the mesh replaces VW's spanning-tree AllReduce."""
    dim = 1 << p.num_bits
    adaptive = p.mode == "adaptive"

    def one_batch(carry, batch):
        weights, bias, acc, t = carry
        idx, val, y, w = batch
        margin = _predict_margin(weights, bias, idx, val)
        gm, loss = _loss_grad(margin, y, w, p.loss_function)
        # per-weight gradients via one segment_sum over the batch's slots
        flat_idx = (idx & (dim - 1)).reshape(-1)
        flat_g = (gm[:, None] * val).reshape(-1)
        gw = jax.ops.segment_sum(flat_g, flat_idx, num_segments=dim)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        gw = gw / denom + p.l2 * weights
        gb = jnp.sum(gm) / denom
        if adaptive:
            # AdaGrad supplies its own per-weight decay (VW --adaptive);
            # stacking the global power_t schedule on top over-decays
            lr_t = p.learning_rate
            acc = acc + gw * gw
            upd = gw / jnp.sqrt(acc + 1e-8)
        else:
            lr_t = p.learning_rate * jnp.power(
                (1.0 + p.initial_t) / (1.0 + p.initial_t + t), p.power_t)
            upd = gw
        weights = weights - lr_t * upd
        if p.l1 > 0:  # truncated-gradient L1 (VW --l1)
            weights = jnp.sign(weights) * jnp.maximum(
                jnp.abs(weights) - lr_t * p.l1, 0.0)
        bias = bias - lr_t * gb
        return (weights, bias, acc, t + 1.0), jnp.sum(loss)

    def one_pass(carry, _):
        weights, bias, acc, t = carry
        (weights, bias, acc, t), losses = jax.lax.scan(
            one_batch, (weights, bias, acc, t), (b_idx, b_val, b_y, b_w))
        if axis_name:
            # per-pass model averaging across workers (the reference's
            # AllReduce at endPass, VowpalWabbitBase.scala:365-369)
            weights = jax.lax.pmean(weights, axis_name)
            bias = jax.lax.pmean(bias, axis_name)
            if adaptive:
                acc = jax.lax.pmean(acc, axis_name)
        return (weights, bias, acc, t), jnp.sum(losses)

    weights = init_w if init_w is not None else jnp.zeros(dim, jnp.float32)
    bias = init_b if init_b is not None else jnp.float32(0.0)
    acc = jnp.zeros(dim, jnp.float32) if adaptive else jnp.zeros((1,), jnp.float32)
    (weights, bias, acc, _), pass_losses = jax.lax.scan(
        one_pass, (weights, bias, acc, jnp.float32(0.0)), None,
        length=p.num_passes)
    return weights, bias, pass_losses


@functools.partial(jax.jit, static_argnames=("p",))
def _fit_bfgs(idx, val, y, w, p: VWParams, init_w, init_b):
    """Full-batch L-BFGS (--bfgs): two-loop recursion with memory m,
    backtracking line search, all inside one jit."""
    dim = 1 << p.num_bits
    m = p.bfgs_memory

    def objective(weights, bias):
        margin = _predict_margin(weights, bias, idx, val)
        _, loss = _loss_grad(margin, y, w, p.loss_function)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        return jnp.sum(loss) / denom + 0.5 * p.l2 * jnp.sum(weights ** 2)

    def grad_fn(weights, bias):
        return jax.grad(objective, argnums=(0, 1))(weights, bias)

    def two_loop(g, s_hist, y_hist, rho_hist, k):
        q = g

        def bwd(i, carry):
            q, alphas = carry
            j = (k - 1 - i) % m
            valid = i < jnp.minimum(k, m)
            alpha = jnp.where(valid, rho_hist[j] * jnp.dot(s_hist[j], q), 0.0)
            q = q - alpha * y_hist[j]
            return q, alphas.at[j].set(alpha)

        q, alphas = jax.lax.fori_loop(0, m, bwd, (q, jnp.zeros(m)))
        # initial Hessian scaling
        j_last = (k - 1) % m
        ys = jnp.dot(y_hist[j_last], y_hist[j_last])
        gamma = jnp.where((k > 0) & (ys > 1e-10),
                          jnp.dot(s_hist[j_last], y_hist[j_last]) / ys, 1.0)
        r = gamma * q

        def fwd(i, r):
            j = (k - jnp.minimum(k, m) + i) % m
            valid = i < jnp.minimum(k, m)
            beta = jnp.where(valid, rho_hist[j] * jnp.dot(y_hist[j], r), 0.0)
            return r + jnp.where(valid, (alphas[j] - beta), 0.0) * s_hist[j]

        return jax.lax.fori_loop(0, m, fwd, r)

    def step(carry, _):
        weights, bias, g, gb, s_hist, y_hist, rho_hist, k = carry
        d = -two_loop(g, s_hist, y_hist, rho_hist, k)

        # backtracking line search on the flattened objective
        def ls_body(carry2):
            alpha, _ = carry2
            return alpha * 0.5, objective(weights + alpha * 0.5 * d,
                                          bias - alpha * 0.5 * gb)

        f0 = objective(weights, bias)
        alpha0 = 1.0
        f1 = objective(weights + alpha0 * d, bias - alpha0 * gb)
        alpha, _ = jax.lax.while_loop(
            lambda c: (c[1] > f0) & (c[0] > 1e-4), ls_body, (alpha0, f1))

        new_w = weights + alpha * d
        new_b = bias - alpha * gb
        ng, ngb = grad_fn(new_w, new_b)
        s = new_w - weights
        yv = ng - g
        sy = jnp.dot(s, yv)
        j = k % m
        ok = sy > 1e-10
        s_hist = jnp.where(ok, s_hist.at[j].set(s), s_hist)
        y_hist = jnp.where(ok, y_hist.at[j].set(yv), y_hist)
        rho_hist = jnp.where(ok, rho_hist.at[j].set(1.0 / jnp.maximum(sy, 1e-10)),
                             rho_hist)
        k = k + jnp.where(ok, 1, 0)
        return (new_w, new_b, ng, ngb, s_hist, y_hist, rho_hist, k), f0

    weights = init_w if init_w is not None else jnp.zeros(dim, jnp.float32)
    bias = init_b if init_b is not None else jnp.float32(0.0)
    g, gb = grad_fn(weights, bias)
    s_hist = jnp.zeros((m, dim), jnp.float32)
    y_hist = jnp.zeros((m, dim), jnp.float32)
    rho_hist = jnp.zeros(m, jnp.float32)
    (weights, bias, *_), losses = jax.lax.scan(
        step, (weights, bias, g, gb, s_hist, y_hist, rho_hist, 0), None,
        length=p.bfgs_iters)
    return weights, bias, losses


def fit_vw(idx: np.ndarray, val: np.ndarray, y: np.ndarray,
           params: VWParams, weights: Optional[np.ndarray] = None,
           initial_model: Optional[tuple] = None,
           num_tasks: int = 0):
    """Train over host arrays; returns (weights, bias, TrainingStats dict).

    Distributed: rows shard over the data mesh, per-pass pmean averaging
    (reference: trainInternalDistributed). initial_model=(w, b) warm-starts
    like setInitialModel (VowpalWabbitBase.scala:354-355).
    """
    import time
    from ...parallel import DATA_AXIS, data_mesh, pad_to_multiple
    t_start = time.perf_counter_ns()
    n = idx.shape[0]
    w_row = (np.ones(n, np.float32) if weights is None
             else np.asarray(weights, np.float32))
    init_w = init_b = None
    if initial_model is not None:
        init_w = jnp.asarray(initial_model[0])
        init_b = jnp.float32(initial_model[1])

    if params.mode == "bfgs":
        w_out, b_out, losses = _fit_bfgs(
            jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y, jnp.float32),
            jnp.asarray(w_row), params, init_w, init_b)
    else:
        import jax as _jax
        nsh = 1
        if num_tasks > 1 or (num_tasks == 0 and _jax.device_count() > 1):
            nsh = num_tasks if num_tasks > 1 else _jax.device_count()
        if nsh > 1:
            mesh = data_mesh(nsh)
            idx_p, _ = pad_to_multiple(idx, nsh)
            val_p, _ = pad_to_multiple(val, nsh)
            y_p, _ = pad_to_multiple(np.asarray(y, np.float32), nsh)
            wr_p, _ = pad_to_multiple(w_row, nsh)  # pad weight 0 -> no loss
            from jax.sharding import PartitionSpec as P
            from jax import shard_map as _smap

            def local_fit(li, lv, ly, lw):
                bi, bv, by, bw, nb_l = _jitless_batches(li, lv, ly, lw,
                                                        params.batch_size)
                return _fit_sgd(bi, bv, by, bw, params, nb_l, init_w, init_b,
                                axis_name=DATA_AXIS)

            mapped = _smap(
                local_fit, mesh=mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None),
                          P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=(P(), P(), P()), check_vma=False)
            w_out, b_out, losses = jax.jit(mapped)(
                jnp.asarray(idx_p), jnp.asarray(val_p), jnp.asarray(y_p),
                jnp.asarray(wr_p))
        else:
            bi, bv, by, bw, nb = _pad_batches(idx, val,
                                              np.asarray(y, np.float32),
                                              w_row, params.batch_size)
            w_out, b_out, losses = _fit_sgd(
                jnp.asarray(bi), jnp.asarray(bv), jnp.asarray(by),
                jnp.asarray(bw), params, nb, init_w, init_b)

    w_np = np.asarray(w_out)
    elapsed = time.perf_counter_ns() - t_start
    denom = max(float(w_row.sum()), 1.0)
    stats = {
        "passes": params.num_passes if params.mode != "bfgs" else params.bfgs_iters,
        "final_loss": float(np.asarray(losses)[-1]) / (denom if params.mode != "bfgs" else 1.0),
        "loss_history": (np.asarray(losses) / (denom if params.mode != "bfgs" else 1.0)).tolist(),
        "time_total_ns": elapsed,
        "num_features_nonzero": int((w_np != 0).sum()),
    }
    return w_np, float(b_out), stats


def _jitless_batches(idx, val, y, w, batch_size):
    """Traced-shape variant of _pad_batches for use inside shard_map."""
    n = idx.shape[0]
    nb = max(1, -(-n // batch_size))
    pad = nb * batch_size - n
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        val = jnp.pad(val, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
    k = idx.shape[1]
    return (idx.reshape(nb, batch_size, k), val.reshape(nb, batch_size, k),
            y.reshape(nb, batch_size), w.reshape(nb, batch_size), nb)


def predict_vw(weights, bias, idx, val, link: Optional[str] = None):
    margins = np.asarray(_predict_margin(jnp.asarray(weights),
                                         jnp.float32(bias),
                                         jnp.asarray(idx), jnp.asarray(val)))
    if link == "logistic":
        return 1.0 / (1.0 + np.exp(-margins))
    return margins
