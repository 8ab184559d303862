"""An untraced run leaves the program's telemetry off: the no-op handle for
the whole run, and not one ``Span`` made."""
import pytest

import bench_testkit as kit
from repro import obs
from repro.obs import trace


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", [kit.SERVED, kit.PLACED])
def test_untraced_run_makes_no_span(tiny, cell, monkeypatch):
    root, bench = tiny
    made = []
    init = trace.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[1] if len(a) > 1 else kw.get("name"))
        init(self, *a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counting)
    noop = obs.get_telemetry()
    assert not noop.enabled

    def still_noop(sut):
        assert obs.get_telemetry() is noop

    res = kit.run(root, bench, cell, trace=False, hook=still_noop)
    assert res["correct"] is True and res["attempted"] > 0
    assert obs.get_telemetry() is noop
    assert made == []
