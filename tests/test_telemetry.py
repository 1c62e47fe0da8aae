"""The control plane's span recorder (`repro.telemetry`) and its spans.

The recorder: nesting with parent and root ids and self time, attributes,
a span closed by an exception, the parent of a span opened on a worker
thread, ring overflow and the reader's refusal, totals over an interval,
and a stress test of many threads on one ring.  Then a small sharded
fleet folds events and re-certifies under ``jax.profiler``: every span of
the control plane comes back from the ``.xplane.pb`` on the host plane,
and from the recorder.
"""
import pathlib
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro import telemetry
from repro.core.binpack import BinType
from repro.core.manager import ResourceManager
from repro.core.profiler import paper_profile_table
from repro.core.shard import hash_cells
from repro.core.strategies import ST3
from repro.core.streams import (
    AnalysisProgram,
    StreamAdded,
    StreamRateChanged,
    StreamRemoved,
    StreamSpec,
)

#: Every span the control plane records on the event fold and the
#: certification path.
PROGRAM_SPANS = (
    "shard.apply_events", "shard.reconstruct", "controller.apply",
    "controller.repair", "controller.exact", "controller.lower_bound",
    "shard.refresh_prices", "colgen.certify", "colgen.prepare",
    "colgen.master_lp", "colgen.exact_dfs", "colgen.price_dp",
    "knapsack.prepare", "knapsack.run", "knapsack.fetch",
    "knapsack.backtrack", "shard.install_prices",
)


def _by_name(rec: telemetry.Recorder) -> dict[str, telemetry.Span]:
    return {r.name: r for r in rec.records()}


def test_nesting_sets_parent_root_and_self_time():
    rec = telemetry.Recorder()
    with rec.span("outer"):
        with rec.span("mid"):
            with rec.span("inner"):
                time.sleep(0.002)
        with rec.span("mid2"):
            pass
    with rec.span("next"):
        pass
    s = _by_name(rec)
    assert s["outer"].parent_id is None and s["outer"].root_id == s["outer"].span_id
    assert s["mid"].parent_id == s["outer"].span_id
    assert s["inner"].parent_id == s["mid"].span_id
    assert s["mid2"].parent_id == s["outer"].span_id
    assert {s[n].root_id for n in ("mid", "inner", "mid2")} == {s["outer"].span_id}
    assert s["next"].parent_id is None and s["next"].root_id == s["next"].span_id

    def dur(r):
        return r.t1 - r.t0

    t = rec.totals()
    assert t["outer"].count == 1
    assert t["outer"].seconds == dur(s["outer"])
    assert t["outer"].self_seconds == pytest.approx(
        dur(s["outer"]) - dur(s["mid"]) - dur(s["mid2"]), abs=1e-12
    )
    assert t["mid"].self_seconds == pytest.approx(dur(s["mid"]) - dur(s["inner"]), abs=1e-12)
    assert t["inner"].self_seconds == dur(s["inner"]) >= 0.002


def test_attributes_given_and_set_in_the_body():
    rec = telemetry.Recorder()
    with rec.span("x", events=3) as attrs:
        attrs["bytes"] = 17
    assert _by_name(rec)["x"].attrs == {"events": 3, "bytes": 17}


def test_an_exception_closes_its_span():
    rec = telemetry.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("boom"):
                raise ValueError("no")
    with rec.span("after"):
        pass
    s = _by_name(rec)
    assert s["boom"].parent_id == s["outer"].span_id
    assert s["boom"].t1 <= s["outer"].t1
    assert s["after"].parent_id is None


def test_a_span_on_a_worker_thread_names_the_submitting_span():
    """A span opened on a worker thread is not nested under the span
    open on the thread that submitted the work: it starts its own tree."""
    rec = telemetry.Recorder()

    def work(i):
        with rec.span(f"inner{i}"):
            return i

    with rec.span("outer"):
        with ThreadPoolExecutor(max_workers=2) as ex:
            assert ex.submit(work, 9).result(timeout=10) == 9
    s = _by_name(rec)
    outer = s["outer"]
    assert s["inner9"].parent_id is None
    assert s["inner9"].root_id == s["inner9"].span_id
    assert rec.totals()["outer"].self_seconds <= outer.t1 - outer.t0


def test_ring_overflow_counts_drops_and_the_reader_refuses():
    rec = telemetry.Recorder(capacity=4)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
    assert rec.dropped == 2
    with pytest.raises(telemetry.SpansDropped):
        rec.records()
    with pytest.raises(telemetry.SpansDropped):
        rec.totals()
    kept = list(rec._ring)
    assert [r.name for r in kept] == ["s2", "s3", "s4", "s5"]
    # An interval that starts after the last dropped span reads whole.
    assert [r.name for r in rec.records(kept[0].t0)] == ["s2", "s3", "s4", "s5"]
    assert telemetry.CAPACITY == 65_536 == telemetry.RECORDER._ring.maxlen


def test_totals_over_an_interval():
    rec = telemetry.Recorder()
    with rec.span("before"):
        pass
    t0 = time.perf_counter()
    for _ in range(3):
        with rec.span("a"):
            with rec.span("b"):
                pass
    t1 = time.perf_counter()
    with rec.span("a"):
        pass
    t = rec.totals(t0, t1)
    assert set(t) == {"a", "b"}
    assert (t["a"].count, t["b"].count) == (3, 3)
    assert t["a"].seconds == pytest.approx(t["a"].self_seconds + t["b"].seconds)
    assert len(rec.records(t0, t1)) == 6


def test_many_threads_lose_no_span():
    rec = telemetry.Recorder(capacity=1000)
    n_threads, per = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with rec.span("s"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.dropped + len(rec._ring) == n_threads * per
    assert len({r.span_id for r in rec._ring}) == len(rec._ring)


# ---------------------------------------------------------- the program

VGG = AnalysisProgram("VGG-16", "vgg16")
ZF = AnalysisProgram("ZF", "zf")
CATALOG = (
    BinType("c4.2xlarge", (8, 15, 0, 0), 0.419),
    BinType("c4.8xlarge", (36, 60, 0, 0), 1.675),
    BinType("g2.2xlarge", (8, 15, 1536, 4), 0.650),
)
KINDS = [(VGG, 0.25), (VGG, 0.2), (ZF, 0.5), (ZF, 2.0), (ZF, 5.0)]


def _events(fleet):
    evs, t = [], 0.0
    for i in range(6):
        t += 0.01
        evs.append(StreamAdded(StreamSpec(f"j{i}", *KINDS[i % len(KINDS)]), at=t))
    for s, rate in zip(fleet[:4], (2.0, 0.5, 5.0, 2.0)):
        t += 0.01
        if s.program == ZF:
            evs.append(StreamRateChanged(s.name, rate, at=t))
        else:
            evs.append(StreamRemoved(s.name, at=t))
    return evs


def test_control_plane_spans_reach_the_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData

    mgr = ResourceManager(CATALOG, paper_profile_table(), max_nodes=20_000)
    ctrl = mgr.sharded_controller(ST3, cell_key=hash_cells(4))
    fleet = [StreamSpec(f"s{i}", *KINDS[i % len(KINDS)]) for i in range(60)]
    ctrl.reset(fleet, at=0.0)
    ctrl.refresh_prices()
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctrl.apply_events(_events(fleet))
        ctrl.refresh_prices()
    finally:
        jax.profiler.stop_trace()
    t1 = time.perf_counter()

    (path,) = pathlib.Path(tmp_path).glob("plugins/profile/*/*.xplane.pb")
    host = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            host.update(ev.name for ev in line.events)
    assert set(PROGRAM_SPANS) <= host, sorted(set(PROGRAM_SPANS) - host)

    recs = telemetry.records(t0, t1)
    names = {r.name for r in recs}
    assert set(PROGRAM_SPANS) <= names
    assert not any(n.startswith("bench.") for n in names)
    roots = [r for r in recs if r.parent_id is None]
    assert {r.name for r in roots} == {"shard.apply_events", "shard.refresh_prices"}
    batch = next(r for r in roots if r.name == "shard.apply_events")
    assert batch.attrs["events"] == 10 and batch.attrs["runs"] >= 1
    folds = [r for r in recs if r.name == "controller.apply"]
    assert len(folds) == 10 and all(r.root_id == batch.span_id for r in folds)
    cert = next(r for r in recs if r.name == "colgen.certify")
    assert cert.attrs["cells"] == ctrl.n_cells
    assert cert.attrs["pool_columns"] == len(ctrl.column_pool.columns()) > 0
    for lp in (r for r in recs if r.name == "colgen.master_lp"):
        a = lp.attrs
        assert set(a) == {"active", "solves", "cache_hits", "cold_solves",
                          "basis_hits", "pivots"}
        assert a["solves"] + a["cache_hits"] == a["active"]
        assert a["cold_solves"] + a["basis_hits"] == a["solves"]
        assert 1 <= a["cold_solves"] <= a["pivots"]
    fetch = [r for r in recs if r.name == "knapsack.fetch"]
    dp = [r for r in recs if r.name == "colgen.price_dp"]
    assert sum(r.attrs["bytes"] for r in fetch) == sum(
        r.attrs["B"] * r.attrs["T"] * r.attrs["S"] for r in dp
    )
    # The pool's public view is the pool itself, read-only.
    cols = ctrl.column_pool.columns()
    assert isinstance(cols, tuple)
    assert cols == tuple(ctrl.column_pool._cols)
    assert all(isinstance(c.bt_name, str) and c.entries for c in cols)
    assert len(cols) == len(ctrl.column_pool)
