"""Seeded weights in the served pytree, made on the device in one call.

The benchmark makes the weights, not the program, so the reference can read
them without taking anything the program made.  The layout is the one the
served decoder takes (``embed.tok``, stacked ``layers``, ``final_ln``); the
harness checks it against the program's own parameter shapes before use.

Scales are chosen so that a random model is neither degenerate nor
chaotic: projections keep unit-variance activations (attention scores of
unit variance), norms carry a random scale, and the tied embedding is
small so the next token is not the current one.  Greedy decoding then
moves between tokens, and a rounding error stays of its own size through
the layers instead of growing until the served token is a random one:
with query and key at twice this scale, bfloat16 serving and a float8
computation both drift to gaps of 2-4 logits at 32 layers and cannot be
told apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (64-bit seeds keep their high bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def param_shapes(m: dict) -> dict:
    """(shape, dtype) of every leaf, keyed like the served pytree."""
    L, d, V = m["n_layers"], m["d_model"], m["vocab"]
    hd, H, KV, F = m["head_dim"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    w = m["dtype"]
    f32 = "float32"
    return {
        "embed": {"tok": ((V, d), w)},
        "layers": {
            "ln1": {"scale": ((L, d), f32)},
            "attn": {
                "wq": ((L, d, H * hd), w),
                "wk": ((L, d, KV * hd), w),
                "wv": ((L, d, KV * hd), w),
                "wo": ((L, H * hd, d), w),
            },
            "ln2": {"scale": ((L, d), f32)},
            "mlp": {
                "w_in": ((L, d, F), w),
                "w_gate": ((L, d, F), w),
                "w_out": ((L, F, d), w),
            },
        },
        "final_ln": {"scale": ((d,), f32)},
    }


def _std(path: str, m: dict) -> float:
    d, F, H, hd = m["d_model"], m["d_ff"], m["n_heads"], m["head_dim"]
    return {
        "tok": 0.02,
        "wq": 1.0 / d**0.5,
        "wk": 1.0 / d**0.5,
        "wv": 1.0 / d**0.5,
        "wo": 1.0 / (H * hd) ** 0.5,
        "w_in": 1.0 / d**0.5,
        "w_gate": 1.0 / d**0.5,
        "w_out": 1.0 / F**0.5,
    }[path]


def make(m: dict, seed: int):
    """All weights of model sizes ``m`` from ``seed``, in one jitted call."""
    shapes = param_shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    )

    def init(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, (shape, dtype)) in zip(keys, flat):
            name = path[-1].key
            if name == "scale":
                v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                v = _std(name, m) * jax.random.normal(k, shape, dtype)
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(seed_key(seed))
