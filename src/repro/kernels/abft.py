"""Algorithm-based fault tolerance (ABFT) checks for the serve kernels.

The classic Huang–Abraham column-checksum identity: for C = A·B,

    e^T · C  ==  (e^T · A) · B        (e = ones)

holds in exact arithmetic, so any corruption of a single C element
breaks the equality by exactly the corrupted delta.  The trace computes
the right side the way Huang–Abraham originally did: the checksum row
e^T·A is *appended to A* and rides the same GEMM as the product, so the
reference costs one extra output row — no second pass over B, which on
a memory-bound decode step is the entire overhead budget.  (At M == 1
the augmentation is known to perturb XLA's GEMV dispatch and the first
output row with it, so the trace falls back to a separate e^T·A GEMV
there; the product matmul itself is never altered — served tokens stay
bitwise identical to an ABFT-off engine.)

In floating point the two sides are *differently ordered* fp32 sums, so
the check compares within a calibrated tolerance (see
``tests/test_sdc.py`` for the calibration property test):

    |e^T·C - (e^T·A)·B|  <=  ATOL + (RTOL + eps(A))·S + eps(C)·(e^T·|C| + |ref|)

where the scale S bounds (e^T·|A|)·|B| per column *without re-reading B*:
``S_j = min(max_k|a_k| · colabs_j, sum_k|a_k| · colmax_j)`` from the
static per-column stats ``colabs_j = sum_k|B_kj|`` / ``colmax_j =
max_k|B_kj|`` that :func:`weight_colstats` precomputes once at engine
init (weights never change while serving; a dynamic |A|·|B| twin would
cost another full weight pass per step).  The RTOL term covers
reordered-fp32 roundoff (relative rms ~= eps32/sqrt2 of the abs-sum
scale, independent of K; RTOL = 1e-5 leaves ~20x margin over the
5-sigma tail).  The eps(dtype) terms charge the one rounding of each C
element — and of the checksum row — to a low-precision output dtype
(bf16 unit roundoff 2^-9 per element; we charge 2^-8 for margin).

For decode attention there is no checksum identity (softmax is
nonlinear), so the check is a sampled *output fingerprint*: recompute k
rows of the paged online-softmax on the XLA twin — which is bitwise
equal to the served twin on equal inputs (the repo's differential
oracle rests on this; the Pallas kernel sums a packed block's tokens in
another order) — and compare.

:class:`AbftTrace` is the trace-scoped recorder the engine installs via
``layers.abft_override``.  It also owns the *fault operand*: an int32
vector threaded through the jitted decode program that can flip one bit
of one designated intermediate, so injection rides the same executable
as clean runs (armed and disarmed steps are bitwise identical programs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Calibrated fp32 checksum tolerance (property-tested in tests/test_sdc.py).
ABFT_RTOL = 1e-5
ABFT_ATOL = 1e-6

# Fault-operand layout: (site, call_idx, row, col, bit, layer, scrub, 0)
# int32.  The transformer backbone scans its layers, so every in-layer
# check site shares one trace-time call_idx across layers; `layer` narrows
# injection to a single layer (-1 = a call outside the scan, e.g. the
# unembed GEMM).  `scrub` (slot 6) is not an injection field: it tells the
# decode program to run the full weight-fingerprint pass this step — the
# host sets it on the ``KernelConfig.scrub_every`` cadence, riding the
# existing operand so armed/disarmed/scrubbed steps share one executable.
FAULT_LEN = 8
FAULT_SCRUB = 6        # operand slot carrying the scrub-this-step flag
FAULT_NONE = 0
FAULT_MATMUL = 1       # flip out[row, col] of matmul call #call_idx
FAULT_ATTENTION = 2    # flip ctx[row, col] of attention call #call_idx
FAULT_OUTER = -1       # `layer` value for checks outside the layer scan

# Rows fingerprinted per attention call in "checksum" mode ("paranoid"
# checks every row).
SAMPLE_ROWS = 4


def no_fault() -> jnp.ndarray:
    """A disarmed fault operand (site FAULT_NONE matches no check site)."""
    return jnp.zeros((FAULT_LEN,), jnp.int32)


def sample_rows(batch: int, mode: str, k: int = SAMPLE_ROWS) -> list[int]:
    """Deterministic row sample for the attention fingerprint."""
    if mode == "paranoid" or batch <= k:
        return list(range(batch))
    return [i * batch // k for i in range(k)]


def weight_sums(params) -> jax.Array:
    """Per-leaf abs-sum fingerprint of a param pytree, as one (n_leaves,)
    fp32 vector.  Compared *exactly* against an init-time baseline (same
    jitted reduction every step, so bitwise reproducible): ABFT checksums
    cannot see weight corruption — both sides of e^T·(A·B) = (e^T·A)·B
    use the corrupted B — so weights get their own detector."""
    leaves = jax.tree_util.tree_leaves(params)
    return jnp.stack(
        [jnp.sum(jnp.abs(x), dtype=jnp.float32) for x in leaves]
    )


def weight_colstats(params) -> dict[str, tuple[jax.Array, jax.Array]]:
    """Static per-column bounds of every matrix-shaped param leaf, for the
    checksum tolerance: ``{"KxN": (colabs, colmax)}`` with
    ``colabs_j = sum_k|w_kj|`` and ``colmax_j = max_k|w_kj|`` (fp32,
    shape (N,)).  Computed once at engine init — weights are immutable
    while serving, so the per-step tolerance never has to re-read them.

    Lookups key on the *shape* of the operand a projection actually
    contracts with, so each leaf registers its trailing-2D slice in both
    orientations (the unembed multiplies by ``tok.T``); leading axes
    (the layer-scan stack) and same-shaped leaves are merged by
    elementwise max — a sound upper bound for whichever slice a given
    call uses, merely looser where shapes collide."""
    stats: dict[str, tuple[jax.Array, jax.Array]] = {}

    def add(key, colabs, colmax):
        if key in stats:
            a0, m0 = stats[key]
            colabs, colmax = jnp.maximum(a0, colabs), jnp.maximum(m0, colmax)
        stats[key] = (colabs, colmax)

    for x in jax.tree_util.tree_leaves(params):
        if x.ndim < 2:
            continue
        K, N = x.shape[-2], x.shape[-1]
        ab = jnp.abs(x.reshape(-1, K, N).astype(jnp.float32))
        add(f"{K}x{N}", jnp.max(jnp.sum(ab, 1), 0), jnp.max(ab, (0, 1)))
        add(f"{N}x{K}", jnp.max(jnp.sum(ab, 2), 0), jnp.max(ab, (0, 2)))
    return stats


def _flip_bit_f32(v: jax.Array, bit: jax.Array) -> jax.Array:
    """Flip one bit of the fp32 representation of scalar ``v``."""
    u = lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    u = u ^ (jnp.uint32(1) << bit.astype(jnp.uint32))
    return lax.bitcast_convert_type(u, jnp.float32)


def _maybe_flip(a2d: jax.Array, fault: jax.Array, site: int, idx: int, gate):
    """Return ``a2d`` with one bit of ``a2d[row % R, col % C]`` flipped when
    the fault operand targets (site, idx); otherwise writes the unchanged
    value back, so the disarmed program is bitwise identical to one with
    no fault plumbing at all.  Bits >= 16 survive the round-trip through
    fp32 exactly for bf16 arrays (bf16 is the top half of fp32).

    ``col == -1`` targets the largest-magnitude element of the row: a
    magnitude-*decreasing* exponent flip on a tiny element produces a
    delta below bf16's legitimate rounding noise — physically undetectable
    by any checksum — so the seeded harness aims where detection is owed."""
    R, C = a2d.shape
    inject = (fault[0] == site) & (fault[1] == idx) & gate
    r = fault[2] % R
    c = jnp.where(
        fault[3] < 0,
        jnp.argmax(jnp.abs(a2d[fault[2] % R].astype(jnp.float32))).astype(
            jnp.int32
        ),
        fault[3] % C,
    )
    v = a2d[r, c]
    fv = _flip_bit_f32(v, fault[4]).astype(a2d.dtype)
    return a2d.at[r, c].set(jnp.where(inject, fv, v))


def _out_eps(dtype) -> float:
    """Per-element rounding charge for a low-precision product output
    (0 for fp32: its roundoff is covered by the RTOL·scale_in term)."""
    if dtype == jnp.float32:
        return 0.0
    if dtype == jnp.bfloat16:
        return 2.0 ** -8
    return float(jnp.finfo(dtype).eps)


def mm_check(x2: jax.Array, w: jax.Array, out2: jax.Array) -> jax.Array:
    """Column-checksum verdict for one 2D matmul: True iff the output's
    column sums disagree with (e^T·x)·w beyond the calibrated tolerance.
    All operands 2D; comparison in fp32.

    This is the *standalone* (re-read-w) form used by the calibration
    property test and as :meth:`AbftTrace.mm`'s fallback when no
    precomputed column stats cover ``w``; the engine's hot path fuses
    the reference into the product GEMM instead."""
    x32 = x2.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    o32 = out2.astype(jnp.float32)
    got = jnp.sum(o32, axis=0)
    ref = jnp.sum(x32, axis=0) @ w32
    scale_in = jnp.sum(jnp.abs(x32), axis=0) @ jnp.abs(w32)
    tol = ABFT_ATOL + ABFT_RTOL * scale_in
    eps = _out_eps(out2.dtype)
    if eps:
        tol = tol + eps * jnp.sum(jnp.abs(o32), axis=0)
    return jnp.any(jnp.abs(got - ref) > tol)


class AbftTrace:
    """Trace-scoped ABFT recorder.

    Built fresh for each decode trace (inside the jitted function), so the
    ``mm_calls``/``attn_calls`` counters advance at *trace time* — the
    fault operand's ``call_idx`` addresses "the N-th matmul of the step"
    stably across retraces.  ``flags`` collects one boolean verdict per
    check; :meth:`any_bad` reduces them for the host.

    The transformer backbone scans its layers, so flags appended inside
    the scan body would leak its trace scope: the body drains them per
    layer via :meth:`drain` into a scanned output, and ``layer`` (set by
    the body to the traced layer index, None outside the scan) gates
    injection to the fault operand's target layer."""

    def __init__(self, mode: str, fault: jax.Array, colstats=None):
        assert mode in ("checksum", "paranoid"), mode
        self.mode = mode
        self.fault = fault
        self.colstats = colstats or {}
        self.mm_calls = 0
        self.attn_calls = 0
        self.layer = None
        self.flags: list[jax.Array] = []

    def _gate(self):
        """Injection gate for the current scope: the fault's target layer
        must match the scanned layer index (or FAULT_OUTER outside)."""
        if self.layer is None:
            return self.fault[5] == jnp.int32(FAULT_OUTER)
        return self.fault[5] == self.layer

    def drain(self) -> jax.Array:
        """OR-reduce and clear the flags accumulated in the current scope
        (called by scan bodies so no tracer outlives its trace)."""
        out = functools.reduce(
            jnp.logical_or, self.flags, jnp.zeros((), jnp.bool_)
        )
        self.flags = []
        return out

    # ------------------------------------------------------------ matmul --
    def mm(self, x, w, impl=None):
        """Compute, verify and possibly fault-inject one ``x @ w``.  The
        checksum row e^T·x is appended to x so the reference rides the
        product GEMM itself (the classical Huang–Abraham construction) —
        per-row independence keeps the product rows bitwise identical to
        the unaugmented matmul, so an ABFT engine serves the same tokens
        as an ABFT-off one.  M == 1 is the observed exception (XLA's
        GEMV dispatch re-blocks when a row is appended): there the
        reference runs as its own GEMV and the product is untouched.
        Returns ``out`` with any injection applied so a flipped bit
        genuinely corrupts the downstream computation."""
        idx = self.mm_calls
        self.mm_calls += 1
        mm_fn = (lambda a, b: a @ b) if impl is None else impl
        x2 = x.reshape(-1, x.shape[-1])
        M = x2.shape[0]
        a32 = jnp.sum(x2.astype(jnp.float32), axis=0)
        a = a32.astype(x2.dtype)
        if M >= 2:
            fused = mm_fn(jnp.concatenate([x2, a[None]], axis=0), w)
            out2, ref = fused[:M], fused[M].astype(jnp.float32)
        else:
            out2 = mm_fn(x2, w)
            ref = mm_fn(a[None], w)[0].astype(jnp.float32)
        out2 = _maybe_flip(out2, self.fault, FAULT_MATMUL, idx, self._gate())
        o32 = out2.astype(jnp.float32)
        got = jnp.sum(o32, axis=0)
        key = f"{w.shape[0]}x{w.shape[1]}"
        if key in self.colstats:
            colabs, colmax = self.colstats[key]
            scale = jnp.minimum(
                jnp.max(jnp.abs(a32)) * colabs,
                jnp.sum(jnp.abs(a32)) * colmax,
            )
            tol = ABFT_ATOL + (ABFT_RTOL + _out_eps(x2.dtype)) * scale
            eps = _out_eps(out2.dtype)
            if eps:
                tol = tol + eps * (jnp.sum(jnp.abs(o32), axis=0) + jnp.abs(ref))
            self.flags.append(jnp.any(jnp.abs(got - ref) > tol))
        else:
            # no static stats for this operand (standalone trace, or an
            # unregistered shape): fall back to the re-read-w tolerance
            self.flags.append(mm_check(x2, w, out2))
        return out2.reshape(x.shape[:-1] + (w.shape[-1],))

    # --------------------------------------------------------- attention --
    def check_paged_attention(self, ctx, q, kpool, vpool, tables, lengths, layer):
        """Fingerprint-check one paged decode-attention output ``ctx``
        (shape (B, KV, G, d)) of layer ``layer`` of the stacked pools by
        recomputing ``k`` sampled rows on the XLA twin, which computes the
        served kernel's recurrence on equal logical contents (bitwise on
        CPU; the kernel may sum a packed block's tokens in another order).
        Returns ``ctx`` with any injection applied."""
        from repro.kernels.flash_attention.ops import decode_attention_paged

        idx = self.attn_calls
        self.attn_calls += 1
        B = ctx.shape[0]
        c2 = ctx.reshape(B, -1)
        c2 = _maybe_flip(c2, self.fault, FAULT_ATTENTION, idx, self._gate())
        ctx = c2.reshape(ctx.shape)
        rows = jnp.asarray(sample_rows(B, self.mode))
        ref = decode_attention_paged(
            q[rows], kpool, vpool, tables[rows], lengths[rows], layer,
            impl="xla",
        ).astype(jnp.float32)
        got = ctx[rows].astype(jnp.float32)
        scale = jnp.max(jnp.abs(ref))
        self.flags.append(
            jnp.any(jnp.abs(got - ref) > ABFT_ATOL + ABFT_RTOL * scale)
        )
        return ctx

    # ------------------------------------------------------------ reduce --
    def any_bad(self) -> jax.Array:
        """Scalar bool: did any check this trace fail?"""
        return functools.reduce(
            jnp.logical_or, self.flags, jnp.zeros((), jnp.bool_)
        )
