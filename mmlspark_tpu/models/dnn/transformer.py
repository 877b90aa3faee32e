"""Transformer encoder with mesh-routable attention.

The reference's deep-net story is inference over imported graphs
(cntk/CNTKModel.scala) + ImageFeaturizer; it has no sequence models at all
(SURVEY.md §5 long-context: ABSENT). This module is the sequence-side
counterpart designed TPU-first: a pure-JAX encoder whose attention op can
run dense on one device or SEQUENCE-PARALLEL over a mesh via
parallel/ring_attention (ring ppermute or Ulysses all-to-all) — the
long-context path is first-class, not bolted on.

Params are an explicit pytree (dict), so DNNModel's generic persistence and
StableHLO export apply unchanged. TransformerSentenceEncoder wraps the
encoder as a pipeline stage: hash-tokenize -> embed -> encode -> mean-pool,
the text analogue of ImageFeaturizer.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ...core import Model, Param, Table
from ...core.params import HasInputCol, HasOutputCol, in_range, one_of


def init_transformer(vocab_size: int, d_model: int = 256, n_heads: int = 8,
                     n_layers: int = 4, d_ff: int = 1024,
                     max_len: int = 2048, seed: int = 0) -> dict:
    """Random-init encoder params (He-style scaling). The reference loads
    pretrained graphs; here weights are an open pytree users can fill from
    any source (e.g. converted checkpoints) — persistence is generic."""
    rng = np.random.default_rng(seed)

    def dense(fan_in, fan_out):
        return (rng.normal(scale=1.0 / np.sqrt(fan_in),
                           size=(fan_in, fan_out)).astype(np.float32))

    params = {
        "embed": rng.normal(scale=0.02, size=(vocab_size, d_model)
                            ).astype(np.float32),
        "pos": rng.normal(scale=0.02, size=(max_len, d_model)
                          ).astype(np.float32),
        "layers": [],
        "final_ln": {"scale": np.ones(d_model, np.float32),
                     "bias": np.zeros(d_model, np.float32)},
        "meta": {"n_heads": n_heads, "d_model": d_model},
    }
    for _ in range(n_layers):
        params["layers"].append({
            "ln1": {"scale": np.ones(d_model, np.float32),
                    "bias": np.zeros(d_model, np.float32)},
            "wq": dense(d_model, d_model), "wk": dense(d_model, d_model),
            "wv": dense(d_model, d_model), "wo": dense(d_model, d_model),
            "ln2": {"scale": np.ones(d_model, np.float32),
                    "bias": np.zeros(d_model, np.float32)},
            "w1": dense(d_model, d_ff), "b1": np.zeros(d_ff, np.float32),
            "w2": dense(d_ff, d_model), "b2": np.zeros(d_model, np.float32),
        })
    return params


def _layer_norm(x, p, eps: float = 1e-6):
    """Layer norm with f32 statistics regardless of activation dtype
    (bf16 mean/variance accumulation loses ~3 decimal digits at d>=1024);
    the result is cast back to the activation dtype. For f32 activations
    this is bit-identical to computing in place."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def transformer_apply(params: dict, tokens, causal: bool = False,
                      attention: str = "dense", mesh=None, key_mask=None,
                      attention_dtype=None):
    """Encode (seq,) int32 tokens -> (seq, d_model) embeddings.

    attention: 'dense' (single device), 'flash' (single device, Pallas
    online-softmax kernel — no (S, S) score matrix in HBM, the long-context
    choice within one chip), 'ring' or 'ulysses' (sequence-parallel over
    `mesh` — seq must divide by the mesh axis).
    key_mask: (seq,) bool excluding padding keys from attention (dense only;
    the sequence-parallel paths take exact-length documents).
    attention_dtype: cast q/k/v to this dtype for the attention op (e.g.
    jnp.bfloat16: the flash kernels' bf16 operands afford larger VMEM
    blocks than f32 ones). Scores and softmax accumulation stay f32 on every path
    (dense, flash, ring, ulysses); the output is cast back to the
    residual dtype.
    """
    import jax
    import jax.numpy as jnp
    from ...parallel.ring_attention import (reference_attention,
                                            ring_attention,
                                            ulysses_attention)

    if key_mask is not None and attention != "dense":
        raise ValueError(
            f"key_mask is only supported with attention='dense'; "
            f"attention={attention!r} would silently ignore it — trim "
            f"padding instead")
    h = params["meta"]["n_heads"]
    d = params["meta"]["d_model"]
    dh = d // h
    seq = tokens.shape[0]
    if seq > params["pos"].shape[0]:
        raise ValueError(
            f"sequence length {seq} exceeds the encoder's max_len "
            f"{params['pos'].shape[0]}; truncate or init with a larger "
            f"max_len")
    x = params["embed"][tokens] + params["pos"][:seq]

    for lp in params["layers"]:
        y = _layer_norm(x, lp["ln1"])
        q = (y @ lp["wq"]).reshape(seq, h, dh)
        k = (y @ lp["wk"]).reshape(seq, h, dh)
        v = (y @ lp["wv"]).reshape(seq, h, dh)
        if attention_dtype is not None:
            q = q.astype(attention_dtype)
            k = k.astype(attention_dtype)
            v = v.astype(attention_dtype)
        if attention == "ring":
            a = ring_attention(q, k, v, mesh=mesh, causal=causal)
        elif attention == "ulysses":
            a = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        elif attention == "flash":
            from ...ops.flash_attention import flash_attention
            a = flash_attention(q, k, v, causal=causal)
        else:
            a = reference_attention(q, k, v, causal=causal,
                                    key_mask=key_mask)
        a = a.astype(x.dtype)
        x = x + a.reshape(seq, d) @ lp["wo"]
        y = _layer_norm(x, lp["ln2"])
        x = x + jax.nn.gelu(y @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    return _layer_norm(x, params["final_ln"])


class TransformerSentenceEncoder(Model, HasInputCol, HasOutputCol):
    """Text -> fixed-size embeddings via hash tokenization + the encoder
    (the text analogue of ImageFeaturizer's layer-cut featurization)."""
    vocab_bits = Param("vocab_bits", "hash-vocabulary bits", 14,
                       validator=in_range(4, 22))
    d_model = Param("d_model", "model width", 128)
    n_heads = Param("n_heads", "attention heads", 8)
    n_layers = Param("n_layers", "encoder blocks", 2)
    d_ff = Param("d_ff", "feed-forward width", 256)
    max_len = Param("max_len", "max tokens per document", 512)
    seed = Param("seed", "init seed", 0)
    attention = Param("attention",
                      "strategy for encode_long (single long documents): "
                      "dense | flash (single-device Pallas, no (S,S) "
                      "matrix) | ring | ulysses (sequence-parallel). Batch "
                      "transform() always runs dense — short docs are "
                      "vmapped, which composes with data sharding, not "
                      "sequence sharding.", "dense",
                      validator=one_of("dense", "flash", "ring", "ulysses"))
    attention_dtype = Param(
        "attention_dtype",
        "cast q/k/v to this dtype inside encode_long's attention "
        "(softmax accumulation stays f32 on every path)", None,
        validator=one_of(None, "bfloat16", "float32"))

    def __init__(self, **kw):
        super().__init__(**kw)
        self._params: Optional[dict] = None
        self._encode_jit = None  # compiled batch encoder (shapes bucketed)

    # -- weights ------------------------------------------------------------
    def _ensure_params(self):
        if self._params is None:
            self._params = init_transformer(
                1 << self.vocab_bits, self.d_model, self.n_heads,
                self.n_layers, self.d_ff, self.max_len, self.seed)
        return self._params

    def set_params_tree(self, params: dict) -> "TransformerSentenceEncoder":
        self._params = params
        self._encode_jit = None
        return self

    def _get_state(self):
        import jax
        p = self._ensure_params()
        no_meta = {k: v for k, v in p.items() if k != "meta"}
        leaves, treedef = jax.tree_util.tree_flatten(no_meta)
        template = init_transformer(
            1 << self.vocab_bits, self.d_model, self.n_heads,
            self.n_layers, self.d_ff, self.max_len, self.seed)
        t_def = jax.tree_util.tree_structure(
            {k: v for k, v in template.items() if k != "meta"})
        if treedef != t_def:
            # load rebuilds the treedef from the Params — a custom tree from
            # set_params_tree would silently rebind leaves; refuse at save
            raise ValueError(
                "params tree structure does not match this stage's "
                "architecture Params (custom set_params_tree layout?); "
                "align the Params with the tree before saving")
        return {f"leaf_{i}": np.asarray(v) for i, v in enumerate(leaves)}

    def _set_state(self, s):
        import jax
        template = init_transformer(
            1 << self.vocab_bits, self.d_model, self.n_heads,
            self.n_layers, self.d_ff, self.max_len, self.seed)
        no_meta = {k: v for k, v in template.items() if k != "meta"}
        _, treedef = jax.tree_util.tree_flatten(no_meta)
        leaves = [np.asarray(s[f"leaf_{i}"]) for i in range(len(s))]
        restored = jax.tree_util.tree_unflatten(treedef, leaves)
        restored["meta"] = template["meta"]
        self._params = restored
        self._encode_jit = None

    # -- tokenization -------------------------------------------------------
    def _tokenize(self, text: str) -> np.ndarray:
        from ...ops.hashing import hash_token
        mask = (1 << self.vocab_bits) - 1
        toks = [hash_token(w) & mask for w in str(text).lower().split()]
        return np.asarray(toks[: self.max_len], np.int32)

    def _compiled_encoder(self):
        """One jitted vmapped encoder, cached on the stage: width is padded
        to a power of two so repeated transforms hit the compile cache."""
        if self._encode_jit is not None:
            return self._encode_jit
        import jax
        import jax.numpy as jnp
        raw = self._ensure_params()
        # meta stays python ints (reshape dims must be static under jit)
        params = {k: (v if k == "meta"
                      else jax.tree_util.tree_map(jnp.asarray, v))
                  for k, v in raw.items()}

        def encode(tokens, length):
            real = jnp.arange(tokens.shape[0]) < length
            # padding is masked OUT of attention, so a doc's embedding is
            # independent of the batch's padded width
            emb = transformer_apply(params, tokens, attention="dense",
                                    key_mask=real)
            m = real[:, None]
            return (emb * m).sum(0) / jnp.maximum(length, 1)

        self._encode_jit = jax.jit(jax.vmap(encode))
        return self._encode_jit

    def _transform(self, t: Table) -> Table:
        import jax.numpy as jnp
        rows = [self._tokenize(v) for v in t[self.input_col]]
        longest = max((len(r) for r in rows), default=1) or 1
        width = 1
        while width < longest:
            width *= 2
        width = min(width, self.max_len)
        batch_tok = np.zeros((len(t), width), np.int32)
        lengths = np.zeros(len(t), np.int32)
        for i, r in enumerate(rows):
            batch_tok[i, :len(r)] = r
            lengths[i] = len(r)
        enc = self._compiled_encoder()(jnp.asarray(batch_tok),
                                       jnp.asarray(lengths))
        return t.with_column(self.output_col,
                             np.asarray(enc, np.float32))

    def encode_long(self, tokens: np.ndarray, mesh=None):
        """Encode ONE long document with the configured attention strategy;
        'ring'/'ulysses' run sequence-parallel over `mesh`."""
        import jax
        import jax.numpy as jnp
        if self.attention in ("ring", "ulysses"):  # flash is single-device
            from ...parallel import data_mesh
            mesh = mesh or data_mesh()
            from ...parallel import DATA_AXIS
            n_dev = mesh.shape[DATA_AXIS]
            if len(tokens) % n_dev:
                raise ValueError(
                    f"attention={self.attention!r} shards the sequence over "
                    f"{n_dev} devices; length {len(tokens)} is not "
                    f"divisible — pad/truncate the document or use "
                    f"attention='dense'")
        raw = self._ensure_params()
        params = {k: (v if k == "meta"
                      else jax.tree_util.tree_map(jnp.asarray, v))
                  for k, v in raw.items()}
        adt = jnp.dtype(self.attention_dtype) if self.attention_dtype \
            else None
        return np.asarray(transformer_apply(
            params, jnp.asarray(tokens, jnp.int32),
            attention=self.attention, mesh=mesh, attention_dtype=adt))
