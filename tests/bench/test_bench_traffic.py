"""The traffic generator: determinism by seed, clips, and equal work per seed."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.traffic import Traffic  # noqa: E402

BENCH = Path(__file__).resolve().parents[2] / "bench"
CFG = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())


CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
LENGTHS = {k: CHAT[k] for k in ("prompt", "output")}
MIXES = {
    "chat": CHAT,
    "offline": {"kind": "backlog", "lead_s": 30, "waiting_per_slot": 2, **LENGTHS},
    "sessions": {"kind": "sessions", "lead_s": 45, "system_prompts": 8, "system_len": 1024,
                 "zipf": 1.1, "turns": [2, 6], "think_mean_s": 3.0,
                 "user": {"median": 96, "sigma": 0.8, "min": 16, "max": 512},
                 "answer": {"median": 160, "sigma": 0.8, "min": 8, "max": 512}},
}


def mix(name):
    return MIXES[name]


def drain(t: Traffic):
    return t.due(1e9, 0)


@pytest.mark.parametrize("name", ["chat", "sessions"])
def test_same_seed_same_requests(name):
    a = drain(Traffic(mix(name), {"rate_per_s": 2.0}, CFG, 2**31 + 17, 30))
    b = drain(Traffic(mix(name), {"rate_per_s": 2.0}, CFG, 2**31 + 17, 30))
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_chat_lengths_inside_clips_and_window_work_equal_across_seeds():
    m = mix("chat")
    lead, secs, rate = m["lead_s"], 30, 4.0
    sets = []
    for seed in (1, 2, 3**20):
        reqs = drain(Traffic(m, {"rate_per_s": rate}, CFG, seed, secs))
        for r in reqs:
            assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
            assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
            assert r.prompt.dtype == np.int32 and r.prompt.max() < CFG["model"]["vocab"]
        win = [r for r in reqs if lead <= r.due < lead + secs]
        assert len(win) == round(rate * secs)
        sets.append((sorted(len(r.prompt) for r in win), sorted(r.max_new for r in win)))
        dues = [r.due for r in reqs]
        assert dues == sorted(dues)
    assert sets[0] == sets[1] == sets[2]


def test_seeds_differ():
    a = drain(Traffic(mix("chat"), {"rate_per_s": 2.0}, CFG, 1, 30))
    b = drain(Traffic(mix("chat"), {"rate_per_s": 2.0}, CFG, 2, 30))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_backlog_keeps_the_queue_full():
    m = mix("offline")
    t = Traffic(m, {}, CFG, 5, 30)
    first = t.due(0.0, 0)
    assert len(first) == m["waiting_per_slot"] * CFG["serve"]["slots"]
    assert len(t.due(1.0, len(first) - 3)) == 3
    assert t.due(2.0, len(first)) == []


def test_sessions_grow_history_and_fit():
    m = mix("sessions")
    t = Traffic(m, {"rate_per_s": 1.0}, CFG, 9, 30)
    first = t.due(m["lead_s"] + 30, 0)
    assert first and all(r.session >= 0 for r in first)
    r = first[0]
    sys_len = m["system_len"]
    assert any(np.array_equal(r.prompt[:sys_len], s) for s in t.system)
    answer = np.arange(r.max_new, dtype=np.int32) % CFG["model"]["vocab"]
    t.finished(r, answer, 100.0)
    nxt = [q for q in t.due(1e9, 0) if q.session == r.session]
    if nxt:   # sessions of one turn end here
        q = nxt[0]
        assert q.due >= 100.0
        np.testing.assert_array_equal(q.prompt[: len(r.prompt)], r.prompt)
        np.testing.assert_array_equal(q.prompt[len(r.prompt): len(r.prompt) + len(answer)], answer)
        assert len(q.prompt) + q.max_new <= CFG["serve"]["max_len"]
