"""Decoder-only LM assembled from the family mixers.

One Model object per config exposing:

    init(key)                          -> params pytree
    loss(params, tokens, labels)       -> scalar CE (+ MoE aux)
    prefill(params, tokens)            -> (logits_last, caches)
    decode_step(params, token, caches) -> (logits, caches)
    init_caches(batch, max_len)        -> caches pytree

Layers are scanned (jax.lax.scan) over stacked parameters so the 512-device
dry-run compiles one layer body; heterogeneous layer patterns are handled
per family:

  * dense / moe:    uniform stack.
  * gemma3 (5 local : 1 global):  uniform params; the per-layer sliding
    window is a scanned int32 input (a huge window == global attention), so
    the pattern costs no extra code paths.
  * rwkv6:          uniform stack of WKV mixers.
  * recurrentgemma: layers grouped (rnn, rnn, attention); the group is
    scanned, remainder layers are applied unrolled.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.arch import layers as L
from repro.arch import moe as M
from repro.arch import rglru as G
from repro.arch import rwkv as R
from repro.configs.base import ModelConfig

GLOBAL_WINDOW = jnp.int32(2**30)  # "window" that never masks = global attn


def remat_policy_of(cfg: ModelConfig):
    """jax.checkpoint policy from the config knob (EXPERIMENTS.md §Perf)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return None  # full remat: save nothing


def layer_windows(cfg: ModelConfig):
    """(n_layers,) int32 sliding windows; 2^30 marks global layers.
    Returned as numpy (static config), converted to jnp at scan sites."""
    import numpy as np

    if cfg.sliding_window is None:
        return np.full((cfg.n_layers,), 2**30, np.int32)
    if not cfg.global_every:
        return np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
    w = []
    for i in range(cfg.n_layers):
        is_global = (i + 1) % cfg.global_every == 0
        w.append(2**30 if is_global else cfg.sliding_window)
    return np.asarray(w, np.int32)


# ------------------------------------------------------------ layer bodies --


def attn_block_init(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "attn": L.attention_init(ks[0], cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = M.moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg)
    return p


def attn_block_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    window: jax.Array | int | None,
    positions: jax.Array | None,
    cache: dict | None,
    ragged_ok: bool | None = None,
) -> tuple[jax.Array, dict | None, jax.Array]:
    h, new_cache = L.multihead_attention(
        p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
        positions=positions, causal=True, window=window, cache=cache,
        ragged_ok=ragged_ok,
    )
    x = x + h
    z = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        f, aux = M.moe_apply(p["moe"], cfg, z)
    else:
        f = L.mlp(p["mlp"], cfg, z)
    return x + f, new_cache, aux


def rwkv_block_init(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "wkv": R.rwkv_init(ks[0], cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.mlp_init(ks[1], cfg),
    }


def rwkv_block_apply(p, cfg, x, *, cache):
    h, new_cache = R.rwkv_mix(p["wkv"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), cache)
    x = x + h
    f = L.mlp(p["mlp"], cfg, L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x + f, new_cache, jnp.zeros((), jnp.float32)


def rnn_block_init(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "rnn": G.rglru_init(ks[0], cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.mlp_init(ks[1], cfg),
    }


def rnn_block_apply(p, cfg, x, *, cache):
    h, new_cache = G.rglru_block(p["rnn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), cache)
    x = x + h
    f = L.mlp(p["mlp"], cfg, L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x + f, new_cache, jnp.zeros((), jnp.float32)


# ------------------------------------------------------------------- model --


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- params --
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        ke, kl, kf = jax.random.split(key, 3)
        params: dict[str, Any] = {"embed": L.embedding_init(ke, cfg)}
        if cfg.family == "hybrid":
            ng, rem = divmod(cfg.n_layers, cfg.rnn_per_attention + 1)
            gkeys = jax.random.split(kl, max(ng, 1))

            def group_init(k):
                ks = jax.random.split(k, cfg.rnn_per_attention + 1)
                return {
                    "rnn": jax.vmap(lambda kk: rnn_block_init(kk, cfg))(
                        ks[: cfg.rnn_per_attention]
                    ),
                    "attn": attn_block_init(ks[-1], cfg),
                }

            params["groups"] = jax.vmap(group_init)(gkeys[:ng])
            rkeys = jax.random.split(kf, max(rem, 1))
            params["tail"] = (
                jax.vmap(lambda kk: rnn_block_init(kk, cfg))(rkeys[:rem])
                if rem
                else {}
            )
        else:
            block_init = (
                rwkv_block_init if cfg.mixer == "rwkv6" else attn_block_init
            )
            lkeys = jax.random.split(kl, cfg.n_layers)
            params["layers"] = jax.vmap(lambda kk: block_init(kk, cfg))(lkeys)
        params["final_ln"] = L.rmsnorm_init(cfg.d_model)
        if cfg.family == "vlm" and cfg.n_patches:
            params["patch_proj"] = jax.nn.initializers.normal(
                0.02, dtype=jnp.dtype(cfg.dtype)
            )(kf, (cfg.patch_dim, cfg.d_model))
        return params

    # ------------------------------------------------------------ forward --
    def _backbone(
        self,
        params: dict,
        x: jax.Array,
        positions: jax.Array | None,
        caches: Any | None,
        remat: bool,
    ) -> tuple[jax.Array, Any, jax.Array]:
        cfg = self.cfg

        if cfg.family == "hybrid":
            return self._backbone_hybrid(params, x, positions, caches, remat)
        if cfg.global_every and caches is not None:
            # local:global mixed caches have heterogeneous sizes -> grouped
            # scan so ring buffers stay window-sized (a 1k-window layer must
            # not allocate 500k slots).
            return self._backbone_local_global(params, x, positions, caches)
        if caches is not None and "kpool" in caches:
            return self._backbone_paged(params, x, positions, caches)

        if cfg.mixer == "rwkv6":
            def body(x, p_c):
                p, c = p_c
                y, nc, aux = rwkv_block_apply(p, cfg, x, cache=c)
                return y, (nc, aux)
        else:
            # the scan traces per-layer windows, so the ragged-decode ring
            # invariant (ring extent <= window, with stacked caches padded
            # to the largest extent) is checked statically here, over ALL
            # scanned layers, and passed down as a hint
            ragged = None
            if caches is not None:
                size = caches["k"].shape[2]
                ragged = bool((layer_windows(cfg) >= size).all())

            def body(x, p_c_w_i):
                p, c, w, li = p_c_w_i
                trace = L.abft_active()
                if trace is not None:
                    trace.layer = li
                y, nc, aux = attn_block_apply(
                    p, cfg, x, window=w, positions=positions, cache=c,
                    ragged_ok=ragged,
                )
                # a scanned body must not leak traced values through the
                # trace's Python-side flag list: drain the layer's ABFT
                # verdicts into a scanned output instead
                flag = (
                    trace.drain() if trace is not None
                    else jnp.zeros((), jnp.bool_)
                )
                return y, (nc, aux, flag)

        if remat:
            body = jax.checkpoint(body, policy=remat_policy_of(cfg))

        if cfg.mixer == "rwkv6":
            xs = (params["layers"], caches)
            x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
        else:
            xs = (
                params["layers"], caches, jnp.asarray(layer_windows(cfg)),
                jnp.arange(cfg.n_layers),
            )
            x, (new_caches, auxs, flags) = jax.lax.scan(body, x, xs)
            trace = L.abft_active()
            if trace is not None:
                trace.layer = None
                trace.flags.append(jnp.any(flags))
        return x, new_caches, jnp.sum(auxs)

    def _backbone_paged(self, params, x, positions, caches):
        """Decode over the paged pools (serve/kvcache.py).  The stacked K/V
        pools are carried through the layer scan, not scanned: a scanned
        pool would be sliced out and restacked every layer, a pool-sized
        copy each way.  Carried, each layer writes its token into the pools
        in place and the kernel reads layer ``li`` of them by index.  The
        block table and lengths are the same for every layer, and so is the
        kernel's work list, built here once.  A row whose table starts at
        the sink holds no request: its length stays 0, so the kernel and
        its XLA twin both read the one sink key, and the list gives it one
        item."""
        from repro.kernels.flash_attention.decode_attention import paged_work_list
        from repro.serve.kvcache import SINK_BLOCK

        cfg = self.cfg
        table, length = caches["table"][0], caches["len"][0]
        rows, lanes = caches["kpool"].shape[2:]
        bs = rows * lanes // (cfg.n_kv_heads * cfg.resolved_head_dim)
        # the kernel attends over the keys through this step's token
        work = paged_work_list(table, length + 1, bs)

        def body(carry, p_w_i):
            x, kpool, vpool = carry
            p, w, li = p_w_i
            trace = L.abft_active()
            if trace is not None:
                trace.layer = li
            cache = {
                "kpool": kpool, "vpool": vpool, "table": table,
                "len": length, "layer": li, "work": work,
            }
            # paged caches exist only for all-global configs
            # (kvcache.supports_paged), so the ragged invariant is free
            y, nc, aux = attn_block_apply(
                p, cfg, x, window=w, positions=positions, cache=cache,
                ragged_ok=True,
            )
            flag = (
                trace.drain() if trace is not None
                else jnp.zeros((), jnp.bool_)
            )
            return (y, nc["kpool"], nc["vpool"]), (aux, flag)

        xs = (
            params["layers"], jnp.asarray(layer_windows(cfg)),
            jnp.arange(cfg.n_layers),
        )
        (x, kpool, vpool), (auxs, flags) = jax.lax.scan(
            body, (x, caches["kpool"], caches["vpool"]), xs
        )
        trace = L.abft_active()
        if trace is not None:
            trace.layer = None
            trace.flags.append(jnp.any(flags))
        live = caches["table"][..., 0] != SINK_BLOCK
        new_caches = {
            **caches, "kpool": kpool, "vpool": vpool,
            "len": caches["len"] + live.astype(jnp.int32),
        }
        return x, new_caches, jnp.sum(auxs)

    def _split_groups(self, params):
        """Reshape stacked layer params (L, ...) into local/global groups."""
        cfg = self.cfg
        ge = cfg.global_every
        ng = cfg.n_layers // ge
        body = ng * ge

        def grouped(leaf):
            g = leaf[:body].reshape((ng, ge) + leaf.shape[1:])
            return g

        g = jax.tree.map(grouped, params["layers"])
        p_local = jax.tree.map(lambda l: l[:, : ge - 1], g)
        p_global = jax.tree.map(lambda l: l[:, ge - 1], g)
        p_tail = jax.tree.map(lambda l: l[body:], params["layers"])
        n_tail = cfg.n_layers - body
        return p_local, p_global, p_tail, n_tail

    def _backbone_local_global(self, params, x, positions, caches):
        cfg = self.cfg
        p_local, p_global, p_tail, n_tail = self._split_groups(params)

        def local_sub(x, pc):
            p, c = pc
            y, nc, _ = attn_block_apply(
                p, cfg, x, window=cfg.sliding_window,
                positions=positions, cache=c,
            )
            return y, nc

        def group_body(x, pcg):
            pl, pg, c = pcg
            x, nlocal = jax.lax.scan(local_sub, x, (pl, c["local"]))
            x, nglobal, _ = attn_block_apply(
                pg, cfg, x, window=None, positions=positions,
                cache=c["global"],
            )
            return x, {"local": nlocal, "global": nglobal}

        x, ngroups = jax.lax.scan(
            group_body, x, (p_local, p_global, caches["groups"])
        )
        ntail = None
        if n_tail:
            x, ntail = jax.lax.scan(local_sub, x, (p_tail, caches["tail"]))
        new_caches = {"groups": ngroups, "tail": ntail}
        return x, new_caches, jnp.zeros((), jnp.float32)

    def _backbone_hybrid(self, params, x, positions, caches, remat):
        cfg = self.cfg

        def group_body(x, p_c):
            p, c = p_c
            aux = jnp.zeros((), jnp.float32)

            def rnn_sub(x, pc):
                pp, cc = pc
                y, nc, _ = rnn_block_apply(pp, cfg, x, cache=cc)
                return y, nc

            x, nrnn = jax.lax.scan(
                rnn_sub, x, (p["rnn"], c["rnn"] if c is not None else None)
            )
            y, nattn, _ = attn_block_apply(
                p["attn"], cfg, x,
                window=cfg.sliding_window, positions=positions,
                cache=c["attn"] if c is not None else None,
            )
            return y, ({"rnn": nrnn, "attn": nattn}, aux)

        if remat:
            group_body = jax.checkpoint(group_body, policy=remat_policy_of(cfg))
        gcaches = caches["groups"] if caches is not None else None
        x, (ngroups, auxs) = jax.lax.scan(
            group_body, x, (params["groups"], gcaches)
        )
        ntail = None
        if params.get("tail"):
            def tail_sub(x, pc):
                pp, cc = pc
                y, nc = (lambda r: (r[0], r[1]))(
                    rnn_block_apply(pp, cfg, x, cache=cc)[:2]
                )
                return y, nc
            tcaches = caches["tail"] if caches is not None else None
            x, ntail = jax.lax.scan(tail_sub, x, (params["tail"], tcaches))
        new_caches = (
            {"groups": ngroups, "tail": ntail} if caches is not None else None
        )
        return x, new_caches, jnp.sum(auxs)

    def logits_fn(
        self, params: dict, x: jax.Array, positions=None, caches=None,
        remat: bool = False,
    ):
        cfg = self.cfg
        x, new_caches, aux = self._backbone(params, x, positions, caches, remat)
        x = L.rmsnorm(params["final_ln"], x, cfg.norm_eps)
        return L.unembed(params["embed"], x), new_caches, aux

    # -------------------------------------------------------------- train --
    def loss(
        self, params: dict, tokens: jax.Array, labels: jax.Array,
        patches: jax.Array | None = None, remat: bool = True,
    ) -> jax.Array:
        cfg = self.cfg
        x = L.embed(params["embed"], tokens)
        if cfg.family == "vlm" and patches is not None:
            px = patches @ params["patch_proj"]
            x = jnp.concatenate([px, x], axis=1)
            labels = jnp.concatenate(
                [jnp.full(px.shape[:2], -1, labels.dtype), labels], axis=1
            )
        logits, _, aux = self.logits_fn(params, x, remat=remat)
        return L.cross_entropy(logits, labels) + 0.01 * aux

    # -------------------------------------------------------------- serve --
    def init_caches(self, batch: int, max_len: int) -> Any:
        # construction lives with the slot-cache machinery in serve/kvcache
        from repro.serve.kvcache import build_caches

        return build_caches(self.cfg, batch, max_len)

    def prefill(
        self, params: dict, tokens: jax.Array, caches: Any,
        patches: jax.Array | None = None,
        last_index: jax.Array | None = None,
    ):
        """last_index: per-row index of the last real token, for prompts
        right-padded to a bucket length (default: the final position)."""
        cfg = self.cfg
        x = L.embed(params["embed"], tokens)
        if cfg.family == "vlm" and patches is not None:
            px = patches @ params["patch_proj"]
            x = jnp.concatenate([px, x], axis=1)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        with L.prefill_aligned():
            logits, caches, _ = self.logits_fn(
                params, x, positions=positions, caches=caches
            )
        if last_index is None:
            return logits[:, -1], caches
        sel = jnp.take_along_axis(
            logits, last_index.astype(jnp.int32)[:, None, None], axis=1
        )[:, 0]
        return sel, caches

    def decode_step(self, params: dict, tokens: jax.Array, caches: Any):
        """tokens: (B, 1) -> (logits (B, V), new caches)."""
        x = L.embed(params["embed"], tokens)
        logits, caches, _ = self.logits_fn(params, x, caches=caches)
        return logits[:, -1], caches
