"""Plain reference of the `gpt2-medium` configuration: the GPT-2 forward pass
and next-token loss in straightforward float32 `jax.numpy`, under
`default_matmul_precision("highest")` (on a TPU a float32 matmul otherwise
runs in bfloat16). No kernels, no remat, no mixed precision; independent of
the package (imports jax only).

It follows the published architecture (Radford et al. 2019; Hugging Face
`GPT2LMHeadModel`): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x `gelu_new` MLP, a final
LayerNorm and a head tied to the token embedding. The departures of the
repo's block are parameters here, so that the reference computes the same
function as the system it is compared with and says where that function
leaves the paper: `attn_bias=False` (no bias on q, k, v and the output
projection) and `ln_eps=1e-6` (published 1e-5); see the configuration file.

Weights: {"wte" (V, d), "wpe" (P, d), "ln_f": {"scale", "bias"},
"blocks": {name: (L, ...)}} with blocks' ln1/ln2 {"scale", "bias"}, wq, wk,
wv, wo (d, d), w1 (d, ff), b1 (ff,), w2 (ff, d), b2 (d,).
"""
import math

import jax
import jax.numpy as jnp


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def block(x, lp, n_head, ln_eps):
    seq, d = x.shape
    dh = d // n_head
    y = layer_norm(x, lp["ln1"], ln_eps)
    q, k, v = ((y @ lp[w]).reshape(seq, n_head, dh).transpose(1, 0, 2)
               for w in ("wq", "wk", "wv"))
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = (probs @ v).transpose(1, 0, 2).reshape(seq, d)
    x = x + att @ lp["wo"]
    y = layer_norm(x, lp["ln2"], ln_eps)
    return x + gelu_new(y @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]


def sequence_loss(weights, tokens, n_head, ln_eps):
    """Summed next-token negative log-likelihood of one (S,) sequence over
    its S - 1 predicted positions."""
    seq = tokens.shape[0]
    x = weights["wte"][tokens] + weights["wpe"][:seq]

    def step(x, lp):
        return block(x, lp, n_head, ln_eps), None

    x, _ = jax.lax.scan(step, x, weights["blocks"])
    x = layer_norm(x, weights["ln_f"], ln_eps)
    logp = jax.nn.log_softmax(x @ weights["wte"].T, axis=-1)
    return -jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1).sum()


def loss(weights, tokens, n_head, ln_eps=1e-5):
    """Mean next-token loss of a (B, S) batch, one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        total = jax.jit(lambda w, t: jax.lax.map(
            lambda s: sequence_loss(w, s, n_head, ln_eps), t).sum())(
                weights, jnp.asarray(tokens, jnp.int32))
    return float(total) / (tokens.shape[0] * (tokens.shape[1] - 1))
