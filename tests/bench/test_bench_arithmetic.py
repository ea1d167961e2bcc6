"""End-to-end arithmetic on hand-built timestamps, and the cost functions."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import types  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import cost  # noqa: E402
from bench.drive import Record  # noqa: E402
from bench.metrics_ctx import end_to_end, percentile  # noqa: E402
from bench.traffic import Req  # noqa: E402


def rec(rid, due, times, status="FINISHED"):
    r = Record(Req(np.zeros(4, np.int32), len(times), 0.0), rid, due, due)
    r.times = list(times)
    r.status = status
    r.tokens = np.zeros(len(times), np.int32)
    return r


def drv(records, kind="poisson"):
    return types.SimpleNamespace(
        records={r.rid: r for r in records},
        done=[r for r in records if r.status],
        traffic=types.SimpleNamespace(kind=kind),
    )


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([3.0], 95) == 3.0


def test_open_loop_ttft_from_due_and_gaps_in_window():
    # window [10, 20); request 0 due before it, 1 and 2 inside, 3 after it
    rs = [
        rec(0, 9.0, [9.5, 10.5, 11.0]),
        rec(1, 10.0, [10.2, 10.4, 10.7]),
        rec(2, 19.0, [19.9, 20.5], status=""),
        rec(3, 20.0, [20.1]),
    ]
    e = end_to_end(drv(rs), 10.0, 20.0)
    assert e["n_requests"] == 2 and e["attempted"] == 2 and e["failed"] == 0
    assert e["values"]["ttft_p95_ms"] == pytest.approx(900.0)      # 19.9 - 19.0
    assert e["values"]["ttft_p50_ms"] == pytest.approx(200.0)      # 10.2 - 10.0
    gaps = [1.0, 0.5, 0.2, 0.3]                                  # later token in window
    assert e["n_gaps"] == len(gaps)
    assert e["values"]["itl_mean_ms"] == pytest.approx(1e3 * sum(gaps) / 4)
    assert e["values"]["itl_p95_ms"] == pytest.approx(1000.0)
    assert e["n_tokens"] == 6                                   # 10.5 11.0 10.2 10.4 10.7 19.9
    assert e["values"]["out_tok_s"] == pytest.approx(0.6)


def test_request_without_first_token_fails():
    rs = [rec(0, 10.0, []), rec(1, 11.0, [11.5], status="FAILED")]
    e = end_to_end(drv(rs), 10.0, 20.0)
    assert e["attempted"] == 2 and e["failed"] == 2


def test_offline_counts_tokens_over_the_window():
    rs = [rec(0, 0.0, [1.0, 2.0, 3.0]), rec(1, 0.0, [2.5, 4.5], status="")]
    e = end_to_end(drv(rs, kind="backlog"), 2.0, 4.0)
    assert e["values"] == {"out_tok_s": pytest.approx(3 / 2.0)}
    assert e["attempted"] == 1 and e["failed"] == 0


M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
     "d_ff": 16, "vocab": 10}


def test_cost_counts_by_hand():
    assert cost.layer_params(M) == 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    flops, nbytes = cost.decode_attention(M, [3, 5])
    assert flops == 4 * 2 * 4 * 2 * 8
    assert nbytes == 2 * (2 * 8 * 2 * 2 + 2 * 2 * 4 * 2) * 2
    f2, b2 = cost.decode_step(M, [3, 5])
    assert f2 == 2 * (2 * cost.layer_params(M) + 80) * 2 + flops
    assert b2 == (2 * cost.layer_params(M) + 80) * 2 + nbytes
    assert cost.prompt_flops(M, 3) == 2 * 2 * cost.layer_params(M) * 3 + 4 * 2 * 4 * 2 * 6 + 160


def test_least_time_names_its_bound():
    pk = cost.peak("TPU v5 lite")
    t, bound = cost.least_time(197e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = cost.least_time(1.0, 819e9, pk)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        cost.peak("TPU v9 imaginary")
