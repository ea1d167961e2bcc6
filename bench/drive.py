"""Drive the serve engine with a cell's traffic and record what happened.

Only the engine's public surface is used: ``Engine(cfg, params, scfg)``,
``submit``, ``step(on_token)``, ``status``, ``pop_result``, ``stats`` and
``pool``.  Every call into the engine sits in a ``TraceAnnotation`` span
(``bench.step``, ``bench.submit``, ``bench.gen``, ``bench.poll``,
``bench.idle``), so a traced run can say what the host was doing while the
device sat idle.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench.traffic import Req, Traffic

clock = time.perf_counter
DRAIN_LIMIT_S = 60.0     # a window's request still waiting this long past the close has failed


def engine_config(cfg: dict):
    """The program's model and serve configurations from a config file."""
    from repro.configs.base import ModelConfig
    from repro.serve.engine import KVConfig, SchedulerConfig, ServeConfig

    m, s = cfg["model"], cfg["serve"]
    mcfg = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"], vocab=m["vocab"],
        head_dim=m["head_dim"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["norm_eps"]), tie_embeddings=True, dtype=m["dtype"],
    )
    scfg = ServeConfig(
        max_len=s["max_len"],
        temperature=0.0,
        scheduler=SchedulerConfig(
            batch=s["slots"], prefill_chunk=s["prefill_chunk"],
            token_budget=s["token_budget"],
        ),
        kv=KVConfig(
            layout="paged", block_size=s["block_size"],
            num_blocks=s["num_blocks"], prefix_sharing=True,
        ),
    )
    return mcfg, scfg


@dataclasses.dataclass
class Step:
    index: int
    t0: float
    t1: float = 0.0
    out_tokens: int = 0
    decode_lens: list = dataclasses.field(default_factory=list)  # live keys per decoded row
    prompt_lens: list = dataclasses.field(default_factory=list)  # prompts installed (first token)


@dataclasses.dataclass
class Record:
    req: Req
    rid: int
    due: float             # host clock
    submitted: float
    times: list = dataclasses.field(default_factory=list)  # host clock of each token
    admitted: float | None = None   # start of the step after which it no longer waited
    hit_tokens: int = 0             # prompt tokens aliased through the prefix index
    status: str = ""
    tokens: np.ndarray | None = None


class Runner:
    """One engine, one traffic, one run: warm-up, lead, window, drain."""

    def __init__(self, engine, traffic: Traffic, cfg: dict, poll: bool):
        self.eng = engine
        self.traffic = traffic
        self.cfg = cfg
        self.poll = poll              # follow queue wait and prefix hits (traced runs)
        self.steps: list[Step] = []
        self.records: dict[int, Record] = {}
        self.done: list[Record] = []
        self._live: dict[int, Record] = {}
        self._waiting: set[int] = set()
        self._step: Step | None = None
        self.late: list[float] = []

    # ------------------------------------------------------------ engine --
    def _on_token(self, rid: int, tok: int, idx: int, done: bool) -> None:
        now = clock()
        rec = self._live[rid]
        rec.times.append(now)
        st = self._step
        st.out_tokens += 1
        plen = len(rec.req.prompt)
        if idx == 0:
            st.prompt_lens.append(plen)
        else:
            st.decode_lens.append(plen + idx)

    def submit(self, req: Req, due: float) -> Record:
        from repro.serve.engine import Request

        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.eng.submit(Request(req.prompt, req.max_new))
        now = clock()
        rec = Record(req, rid, due, now)
        self.records[rid] = rec
        self._live[rid] = rec
        self._waiting.add(rid)
        self.late.append(now - due)
        return rec

    def step(self) -> None:
        from repro.serve.engine import TERMINAL_STATUSES, RequestStatus

        if self.poll and self._waiting:
            self._prefix_matches()
        st = Step(len(self.steps), clock())
        self._step = st
        with jax.profiler.TraceAnnotation("bench.step", step=st.index):
            self.eng.step(self._on_token)
        st.t1 = clock()
        self.steps.append(st)
        if self.poll:
            with jax.profiler.TraceAnnotation("bench.poll"):
                for rid in list(self._waiting):
                    if self.eng.status(rid) not in (RequestStatus.WAITING, RequestStatus.PREEMPTED):
                        self.records[rid].admitted = st.t0
                        self._waiting.discard(rid)
        else:
            self._waiting = {r for r in self._waiting if not self.records[r].times}
        for rid in [r for r in self._live if self.eng.status(r) in TERMINAL_STATUSES]:
            self._collect(rid, st.t1)

    def _prefix_matches(self) -> None:
        """Prompt tokens the prefix index would alias for each waiting
        request, read from the public pool before the step admits it."""
        pool = self.eng.pool
        bs = pool.block_size
        with jax.profiler.TraceAnnotation("bench.poll"):
            for rid in self._waiting:
                rec = self.records[rid]
                full, tail = pool.match_prefix(rec.req.prompt.tolist())
                tail_len = len(rec.req.prompt) % bs if tail is not None else 0
                rec.hit_tokens = len(full) * bs + tail_len

    def _collect(self, rid: int, now: float) -> None:
        rec = self._live.pop(rid)
        res = self.eng.pop_result(rid)
        rec.status = res.status.value
        rec.tokens = np.asarray(res.tokens, np.int32)
        self.done.append(rec)
        self.traffic.finished(rec.req, rec.tokens, now - self.t_traffic)

    @property
    def waiting(self) -> int:
        """Submitted requests that have no first token yet."""
        return len(self._waiting)

    @property
    def busy(self) -> bool:
        return bool(self._live)

    # -------------------------------------------------------------- runs --
    def warm_up(self, max_prompt: int) -> None:
        """Compile every program the cell's traffic can reach: one request
        per packed block count up to the longest prompt, and one pair of
        equal prompts whose shared partial tail forces a copy-on-write."""
        bs = self.cfg["serve"]["block_size"]
        rng = np.random.default_rng(0)
        vocab = self.cfg["model"]["vocab"]
        n_blocks = -(-max_prompt // bs)
        self.t_traffic = clock()
        reqs = [Req(rng.integers(0, vocab, (n - 1) * bs + 1).astype(np.int32), 2, 0.0)
                for n in range(1, n_blocks + 1)]
        twin = rng.integers(0, vocab, bs + bs // 2 + 1).astype(np.int32)
        reqs += [Req(twin, 4, 0.0), Req(twin.copy(), 4, 0.0)]
        for r in reqs:
            self.submit(r, clock())
        while self.busy:
            self.step()
        self.steps.clear()
        self.done.clear()
        self.records.clear()
        self.late.clear()

    def run(self, seconds: float, on_open=None, on_close=None, tick=None) -> tuple[float, float]:
        """Serve the traffic: lead, ``seconds`` of window, then drain until
        every request due in the window has its first token.  Returns the
        window's open and close on the host clock."""
        tr = self.traffic
        self.t_traffic = t0 = clock()
        opened = t0 + tr.lead
        closed = opened + seconds
        is_open = False
        offline = tr.kind == "backlog"
        while True:
            now = clock()
            if tick:
                tick(now)
            if not is_open and now >= opened:
                is_open = True
                if on_open:
                    on_open()
            with jax.profiler.TraceAnnotation("bench.gen"):
                batch = tr.due(now - t0, len(self._waiting))
            for r in batch:
                self.submit(r, t0 + r.due)
            if now >= closed:
                if on_close:
                    on_close()
                    on_close = None
                if offline or now > closed + DRAIN_LIMIT_S:
                    break
                if all(r.times for r in self.records.values() if opened <= r.due < closed):
                    break
            if self.busy:
                self.step()
                continue
            nxt = tr.next_due()
            wait = 0.001 if nxt is None else max(0.0, min(t0 + nxt, closed) - clock())
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(min(wait, 0.05))
        return opened, closed
