"""The readers of the solve path's spans and compile counters, on a
record made by hand, and on records of a program that lacks them."""

import os

import pytest

from benchmark import run as R

READERS = ("jit_ms.solve", "fetch_wait_ms.solve", "enqueue_ms.round",
           "cert_assemble_s", "cert_eigsolve_s")


def _hist(span, total, **labels):
    return {"labels": {"span": span, **labels}, "counts": [],
            "sum": total, "count": 1}


def _compile(value, span, stage="backend"):
    return {"labels": {"stage": stage, "program": "jit_f", "span": span},
            "value": value}


def _rec(program):
    return {"driver": {"solves": 2, "rounds": [100, 300]},
            "program": program}


def _program(scale):
    """Snapshots whose series grow by ``scale`` x the before values."""
    def snap(k):
        return {
            "span_seconds": {"kind": "histogram", "series": [
                _hist("rbcd.fetch", 1.0 * k, what="word"),
                _hist("rbcd.fetch", 3.0 * k, what="epilogue"),
                _hist("rbcd.enqueue", 2.0 * k, what="rounds"),
                _hist("cert.f64.assemble", 0.5 * k),
                _hist("cert.f64.sa", 0.25 * k),
                _hist("cert.f64.lobpcg", 1.0 * k),
                _hist("cert.f64.shift_invert", 2.75 * k),
                _hist("cert.f64", 5.0 * k)]},
            "jax_compile_seconds_total": {"kind": "counter", "series": [
                _compile(0.5 * k, "rbcd.enqueue"),
                _compile(0.25 * k, "rbcd.enqueue", stage="trace"),
                _compile(1.25 * k, "none")]},
        }
    return {"counters_before": snap(1.0), "counters": snap(1.0 + scale)}


# The window grew every series by its before value (scale 1).
EXPECT = {
    "jit_ms.solve": 1e3 * 2.0 / 2,
    "fetch_wait_ms.solve": 1e3 * 4.0 / 2,
    "enqueue_ms.round": 1e3 * (2.0 - 0.75) / 400,
    "cert_assemble_s": 0.5 / 2,
    "cert_eigsolve_s": 4.0 / 2,
}


def _reader(name):
    return R.load_module(os.path.join(R.HERE, "metrics", name + ".py"),
                         "m_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", READERS)
def test_program_reader(name):
    assert _reader(name).read(_rec(_program(1.0))) == \
        pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", READERS)
def test_program_reader_without_its_family(name):
    """The parent program has no spans or compile counters: no reading."""
    bare = {"counters_before": {}, "counters": {
        "cert_f64_fallback_seconds_total": {"series": []}}}
    assert _reader(name).read(_rec(bare)) is None
    assert _reader(name).read(_rec({})) is None


def test_family_present_but_span_never_ran():
    """A window whose solves never refused the f32 certificate: the f64
    stages read zero, not nothing."""
    prog = _program(1.0)
    for snap in prog.values():
        snap["span_seconds"]["series"] = [
            s for s in snap["span_seconds"]["series"]
            if not s["labels"]["span"].startswith("cert.")]
    assert _reader("cert_assemble_s").read(_rec(prog)) == 0.0
    assert _reader("cert_eigsolve_s").read(_rec(prog)) == 0.0


def test_readers_are_declared():
    import json

    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = declared[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "solve_s"
        assert m["workloads"] == ["city10000.certified"]
