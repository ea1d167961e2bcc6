"""Plain float32 reference of a dense Llama-architecture decoder.

Follows the published description (Touvron et al. 2023, arXiv:2302.13971;
Hugging Face ``LlamaForCausalLM``): token embedding; per layer
``x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x))`` and
``x += W_out(silu(W_gate·n2(x)) * W_in·n2(x))`` with RMSNorm ``n``;
a final RMSNorm and the tied embedding as the output head.  RoPE rotates
the two halves of each head (``rotate_half``) with frequencies
``theta^(-2i/head_dim)``; grouped-query attention gives query head ``h``
the key/value head ``h // (n_heads / n_kv_heads)``.  No biases.

It imports nothing of the program.  Weights come in the served pytree and
dtype and are cast to float32 one layer at a time inside the jitted layer,
so a model whose served weights fill most of the chip still fits beside
them.  Every matmul runs at ``precision=HIGHEST``.

``quant="fp8"`` is the control: the same computation with every matmul
operand rounded to float8 e4m3 (per output channel for weights, per row
for activations), the precision below the bfloat16 the configuration
serves in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block, so scores stay small
E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, N) in float32, operands optionally in fp8."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, D): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, quant):
    """Causal GQA attention; q (T, H, D), k/v (T, KV, D) -> (T, H*D)."""
    T, H, D = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    kpos = jnp.arange(T)
    outs = []
    for s0 in range(0, T, Q_BLOCK):
        qb = q[s0 : s0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / jnp.sqrt(jnp.float32(D))
        qpos = s0 + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant == "fp8":
            p = _fp8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0).reshape(T, H * D)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(layers, i, x, m, quant):
    f32 = lambda a: a[i].astype(jnp.float32)  # noqa: E731
    p = jax.tree.map(f32, layers)
    T = x.shape[0]
    H, KV, D = m.n_heads, m.n_kv_heads, m.head_dim
    pos = jnp.arange(T)
    h = _rmsnorm(x, p["ln1"]["scale"], m.eps)
    q = _rope(_mm(h, p["attn"]["wq"], quant).reshape(T, H, D), pos, m.theta)
    k = _rope(_mm(h, p["attn"]["wk"], quant).reshape(T, KV, D), pos, m.theta)
    v = _mm(h, p["attn"]["wv"], quant).reshape(T, KV, D)
    x = x + _mm(_attention(q, k, v, quant), p["attn"]["wo"], quant)
    h = _rmsnorm(x, p["ln2"]["scale"], m.eps)
    gate = jax.nn.silu(_mm(h, p["mlp"]["w_gate"], quant))
    x = x + _mm(gate * _mm(h, p["mlp"]["w_in"], quant), p["mlp"]["w_out"], quant)
    return x


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(tok, tokens, m):
    return tok[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(tok, final_scale, x, m, quant):
    h = _rmsnorm(x, final_scale, m.eps)
    return _mm(h, tok.astype(jnp.float32).T, quant)


class Sizes:
    """Hashable model sizes, so jitted layers compile once per model."""

    def __init__(self, model: dict):
        self.n_layers = model["n_layers"]
        self.n_heads = model["n_heads"]
        self.n_kv_heads = model["n_kv_heads"]
        self.head_dim = model["head_dim"]
        self.eps = float(model["norm_eps"])
        self.theta = float(model["rope_theta"])
        self._key = tuple(sorted(model.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Sizes) and self._key == other._key


def logits(params: dict, model: dict, tokens: jax.Array, quant: str | None = None):
    """Next-token logits (T, V) in float32 at every position of ``tokens``."""
    m = Sizes(model)
    x = _embed(params["embed"]["tok"], tokens, m)
    for i in range(m.n_layers):
        x = _layer(params["layers"], jnp.int32(i), x, m, quant)
    return _head(params["embed"]["tok"], params["final_ln"]["scale"], x, m, quant)
