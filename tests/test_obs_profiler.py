"""repro.obs mirrored into jax.profiler: while a profiler session is on,
obs spans land in the trace as ``repro.<name>`` annotations; off, the
null span is the one shared object. On the split path: the span
nesting per request, results unchanged by tracing or recording, named
head and tail programs, and their retrace counts."""
import glob
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.partition import cut_points
from repro.models import init
from repro.obs import jaxmon
from repro.serving import SplitServingEngine
from tests.conftest import make_batch

SPLIT_SPANS = ["repro.split.head", "repro.split.link", "repro.split.tail"]


@pytest.fixture(scope="module")
def split():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    batch = make_batch(cfg, B=1, S=8)
    del batch["targets"]
    return cfg, params, batch


def engine(split):
    cfg, params, _ = split
    return SplitServingEngine(cfg, params, versions=("bf16", "w8"))


def host_events(log_dir):
    """(name, start_ns, end_ns, stats) of the repro.* events of the one
    trace under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("repro.")]
    return out


def traced(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("version", ["w8", "bf16"])
def test_split_spans_nest_in_the_profiler_trace(split, tmp_path, version):
    cfg, _, batch = split
    eng, cut = engine(split), cut_points(cfg)[0]
    eng.infer(batch, cut, version)                  # compile outside
    logits, _ = traced(tmp_path, lambda: jax.block_until_ready(
        eng.infer(batch, cut, version)))
    events = host_events(tmp_path)
    (infer,) = [e for e in events if e[0] == "repro.split.infer"]
    assert infer[3]["version"] == version
    assert infer[3]["S"] == batch["tokens"].shape[1]
    assert infer[3]["cut"] == str(cut)
    inner = sorted((e for e in events if e[0] != "repro.split.infer"),
                   key=lambda e: e[1])
    assert [e[0] for e in inner] == SPLIT_SPANS
    assert all(infer[1] <= e[1] and e[2] <= infer[2] for e in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_null_span_is_shared_outside_a_profiler_session(monkeypatch):
    assert isinstance(obs.get_recorder(), obs.NullRecorder)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("split.infer", version="w8") is obs.span("split.head")
    # before jax is imported the check resolves nothing and mirrors nothing
    from repro.obs import events
    monkeypatch.setattr(events, "_profiling", events._resolve_profiling)
    monkeypatch.delitem(sys.modules, "jax")
    assert obs.span("split.head") is obs.span("split.tail")
    assert events._profiling is events._resolve_profiling


def test_logits_identical_with_profiler_and_recorder(split, tmp_path):
    cfg, _, batch = split
    eng, cut = engine(split), cut_points(cfg)[-1]
    for version in ("w8", "bf16"):
        plain, nbytes = eng.infer(batch, cut, version)
        on, _ = traced(tmp_path / version, lambda: jax.block_until_ready(
            eng.infer(batch, cut, version)))
        with obs.recording() as rec:
            recorded, _ = eng.infer(batch, cut, version)
        with obs.recording():
            both, _ = traced(tmp_path / f"{version}-rec", lambda:
                             jax.block_until_ready(
                                 eng.infer(batch, cut, version)))
        for got in (on, recorded, both):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(plain))
        spans = [e["name"] for e in rec.events if e["type"] == "span"]
        # spans emit at exit: the children, then split.infer
        assert spans == ["split.head", "split.link", "split.tail",
                         "split.infer"]
        (link_bytes,) = [m for m in rec.metrics.snapshot()
                         if m["name"] == "split.link_bytes"]
        assert link_bytes["value"] == nbytes
        assert link_bytes["labels"] == {"version": version}


def test_head_and_tail_are_named_and_count_their_traces(split):
    cfg, _, batch = split
    eng = engine(split)
    cuts = cut_points(cfg)[:2]

    def counts():
        c = jaxmon.trace_counts()
        return c.get("split.head", 0), c.get("split.tail", 0)

    for cut, version in [(cuts[0], "w8"), (cuts[1], "w8"),
                         (cuts[1], "bf16")]:
        before = counts()
        eng.infer(batch, cut, version)
        assert counts() == (before[0] + 1, before[1] + 1)
        eng.infer(batch, cut, version)              # a repeat: no trace
        assert counts() == (before[0] + 1, before[1] + 1)

    params = eng._params_for("w8")
    head, tail = eng._fns(cuts[0], "w8")
    assert "jit_split_head" in head.lower(params, batch).as_text()
    act = head(params, batch)
    assert "jit_split_tail" in tail.lower(params, act, batch).as_text()
