"""The readers of the program's spans, on records made by hand.

Each reader returns its number from the spans it names, and ``None`` (the
metric is left out of the line) when the record holds none of them, as a
program without those spans leaves it.
"""
import pathlib

import pytest

from bench import harness

REPO = pathlib.Path(__file__).resolve().parents[2]


def reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py")


def record(*spans):
    return {"spans": [dict(name=n, t0=t0, t1=t1, attrs={})
                      for n, t0, t1 in spans]}


OTHERS = [("submit", 0.0, 0.5), ("solve", 1.0, 1.2), ("resolve", 2.0, 2.1)]


@pytest.mark.parametrize("metric,span,durations_s,want_ms", [
    # p99 by linear interpolation: 1 + 0.99 * 99 ms over 1..100 ms
    ("validate_p99_ms.served", "validate",
     [i / 1e3 for i in range(1, 101)], 99.01),
    ("cache_put_ms.served", "cache/put", [0.010, 0.020, 0.030], 20.0),
    ("solve_wait_ms.served", "solve/wait", [0.040, 0.050], 45.0),
])
def test_reader_value(metric, span, durations_s, want_ms):
    spans = [(span, 10.0 + i, 10.0 + i + d)
             for i, d in enumerate(durations_s)]
    got = reader(metric).read(record(*OTHERS, *spans))
    assert got == pytest.approx(want_ms, rel=1e-6)


@pytest.mark.parametrize("metric", ["validate_p99_ms.served",
                                    "cache_put_ms.served",
                                    "solve_wait_ms.served"])
def test_reader_without_its_span_is_none(metric):
    assert reader(metric).read(record(*OTHERS)) is None
    assert reader(metric).read({"spans": None}) is None
