"""The roots' profiler anchors (``tracing.trace_scope``): with tracing
off no profiler annotation is made, and nothing beyond the ``_events``
check runs; with it on, every take or restore root enters exactly one
``tpusnapshot.<kind>`` annotation, which carries the span file's clock
at its start (``ts_us``) and the root's trace id."""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import Snapshot, tracing


class _Holder:
    def __init__(self, sd):
        self.sd = sd

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        self.sd = sd


class _Recorded:
    """Stands in for ``jax.profiler.TraceAnnotation`` and keeps what it
    was made with, and when it was entered."""

    made = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        self.clock = time.monotonic()
        _Recorded.made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorded(monkeypatch):
    _Recorded.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorded)
    assert not tracing.enabled()
    yield _Recorded.made
    if tracing.enabled():
        tracing.disable()


def _app():
    return {"m": _Holder({"w": jnp.arange(64, dtype=jnp.float32), "n": np.ones(3)})}


def _take_async_take_restore(tmp_path):
    Snapshot.take(str(tmp_path / "a"), _app())
    Snapshot.async_take(str(tmp_path / "b"), _app()).wait()
    target = {"m": _Holder({"w": jnp.zeros(64, jnp.float32), "n": np.zeros(3)})}
    Snapshot(str(tmp_path / "a")).restore(target)
    assert np.array_equal(np.asarray(target["m"].sd["w"]), np.arange(64))


def test_tracing_off_makes_no_annotation(tmp_path, recorded, monkeypatch):
    def refuse(*_a, **_k):
        raise AssertionError("an anchor was made with tracing off")

    monkeypatch.setattr(tracing, "_anchor", refuse)
    _take_async_take_restore(tmp_path)
    assert recorded == []


def test_tracing_on_enters_one_anchor_a_root(tmp_path, recorded):
    path = str(tmp_path / "spans.json")
    tracing.enable(path)
    began = time.monotonic()
    _take_async_take_restore(tmp_path)
    tracing.disable()
    assert [a.name for a in recorded] == [
        "tpusnapshot.take",
        "tpusnapshot.async_take",
        "tpusnapshot.restore",
    ]
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    roots = {
        ev["name"]: ev
        for ev in events
        if ev.get("ph") == "b" and ev["name"] in ("Snapshot.take", "Snapshot.restore")
    }
    for anchor in recorded:
        assert set(anchor.kwargs) == {"trace", "ts_us"}
        assert anchor.kwargs["trace"].startswith(anchor.name.split(".", 1)[1] + "-")
        # The reading is the span file's clock as the annotation is made.
        assert anchor.kwargs["ts_us"] == pytest.approx(
            (anchor.clock - tracing._t0) * 1e6, abs=1000
        )
        assert anchor.kwargs["ts_us"] >= (began - tracing._t0) * 1e6
    # The root span opens inside its anchor and names the same trace.
    for anchor in (recorded[0], recorded[2]):
        root = roots["Snapshot." + anchor.name.split(".", 1)[1]]
        assert root["args"]["trace"] == anchor.kwargs["trace"]
        assert root["ts"] >= anchor.kwargs["ts_us"]


def test_trace_scope_yields_its_id_and_restores_the_context(recorded, tmp_path):
    assert tracing.current_trace_id() is None
    with tracing.trace_scope("restore") as off_id:
        assert tracing.current_trace_id() == off_id
    tracing.enable(str(tmp_path / "t.json"))
    with tracing.trace_scope("take") as outer:
        with tracing.trace_scope("restore") as inner:
            assert tracing.current_trace_id() == inner != outer
        assert tracing.current_trace_id() == outer
    assert tracing.current_trace_id() is None
    assert [a.kwargs["trace"] for a in recorded] == [outer, inner]
