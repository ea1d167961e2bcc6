"""Decide ``correct``: served tokens against the float32 reference.

After the window, a sample of the finished requests, drawn from the seed
and always holding the longest, is run through the reference once each,
teacher-forced on prompt plus served tokens.  At the position that produced
each served token the reference's logits give a gap: its best logit minus
the logit of the token that was served.  The widest gap over the sample is
compared with the cell's limit (``max_logit_gap`` in ``bench/cells``).
Greedy serving in the configuration's precision gives gaps of rounding
size; a wrong token, a wrong position or a wrong layer gives gaps of the
size of the logits' spread.

The control puts the reference, computed in float8 (``quant="fp8"``), in the
program's place: at each position of the same prompts and served tokens the
token the float8 computation ranks first takes the served token's place, and
its gaps are judged by the same limit, so a control run is not correct.  The
program's own widest gap is then kept beside it as ``program_gap``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_llama

SAMPLE_TOKENS = 384   # served tokens the sample reaches (with the longest request)
SAMPLE_MAX = 6        # requests at most


def sample(done: list, seed: int) -> list:
    """Finished requests to check: the longest, then others in an order
    drawn from the seed, until ``SAMPLE_TOKENS`` served tokens."""
    ok = [r for r in done if r.status == "FINISHED" and r.tokens is not None and len(r.tokens)]
    if not ok:
        return []
    ok.sort(key=lambda r: (len(r.req.prompt) + len(r.tokens), r.rid))
    picked = [ok.pop()]
    order = np.random.default_rng(seed).permutation(len(ok))
    for i in order:
        if sum(len(r.tokens) for r in picked) >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(ok[i])
    return picked


@jax.jit
def _gaps(ref, targets, ctrl=None):
    """Per position: the reference's best logit minus its logit of the
    target (or, with ``ctrl``, of the token ``ctrl`` ranks first)."""
    best = jnp.max(ref, axis=-1)
    if ctrl is not None:
        targets = jnp.argmax(ctrl, axis=-1)
    return best - jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]


def gaps(params, model: dict, max_len: int, prompt, served, control: bool = False):
    """Gaps at every served position of one request; with ``control``, also
    the control's gaps there.  Sequences are padded to ``max_len`` so the
    reference compiles once."""
    plen, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    T = int(max_len)
    pad = np.zeros((T,), np.int32)
    pad[: len(seq)] = seq
    targets = np.zeros((T,), np.int32)
    targets[plen - 1 : plen - 1 + n] = served
    toks = jnp.asarray(pad)
    ref = dense_llama.logits(params, model, toks)
    g = np.asarray(_gaps(ref, jnp.asarray(targets)))[plen - 1 : plen - 1 + n]
    if not control:
        return g, None
    ctl = dense_llama.logits(params, model, toks, quant="fp8")
    c = np.asarray(_gaps(ref, jnp.asarray(targets), ctl))[plen - 1 : plen - 1 + n]
    return g, c


def check(params, cfg: dict, done: list, cell: dict, seed: int, control: bool = False) -> dict:
    """The verdict on one run: ``correct`` and the numbers compared.  With
    ``control`` the float8 control is judged in the program's place."""
    model, max_len = cfg["model"], cfg["serve"]["max_len"]
    picked = sample(done, seed)
    limit = float(cell["max_logit_gap"])
    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in picked:
        g, c = gaps(params, model, max_len, r.req.prompt, r.tokens, control)
        n += len(g)
        worst = max(worst, float(np.max(g)))
        if c is not None:
            worst_ctl = max(worst_ctl, float(np.max(c)))
    judged = worst_ctl if control else worst
    out = {
        "correct": bool(picked) and bool(np.isfinite(judged)) and judged <= limit,
        "compared": {"max_logit_gap": {"value": judged, "limit": limit}},
        "checked_tokens": n,
        "checked_requests": len(picked),
    }
    if control:
        out["program_gap"] = worst
    return out
