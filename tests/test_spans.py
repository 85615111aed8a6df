"""Spans and counters inside the serving path (repro.serve.spans): the
recorder's arithmetic, the engine's exact counters on known batches, the
`kbest.dispatch` spans serve_loop leaves in a profiler trace, and the
named scopes in each bucket's compiled program."""
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro.core.index import KBest
from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                              QuantConfig, SearchConfig)
from repro.data.vectors import make_dataset
from repro.serve import Request, SearchEngine, serve_loop
from repro.serve.spans import Recorder


@pytest.fixture(scope="module")
def ds():
    return make_dataset("deep_like", n=300, n_queries=16, k=10)


@pytest.fixture(scope="module")
def graph(ds):
    cfg = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric,
        build=BuildConfig(M=8, knn_k=16, builder="brute", refine_iters=0,
                          reorder="none"),
        search=SearchConfig(L=32, k=10, beam_width=2, et_patience=4))
    return KBest(cfg).add(ds.base)


@pytest.fixture(scope="module")
def ivf(ds):
    return KBest(IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric, index_type="ivf",
        ivf=IVFConfig(kmeans_iters=4, list_pad=32),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=4),
        search=SearchConfig(L=64, k=10, nprobe=4))).add(ds.base)


# ------------------------------------------------------------- recorder
def _sleep_ms(ms):
    t = time.perf_counter()
    while time.perf_counter() - t < ms * 1e-3:
        pass


def test_nesting_gives_self_time_and_max():
    rec = Recorder()
    with rec.span("outer") as outer:
        _sleep_ms(2)
        for _ in range(2):
            with rec.span("inner"):
                with rec.span("leaf"):
                    _sleep_ms(3)
    s = rec.spans()
    assert s["outer"].count == 1 and s["inner"].count == 2
    assert s["leaf"].count == 2
    assert s["outer"].total_ms == pytest.approx(outer.ms)
    # self time is the duration less the direct children only
    assert s["outer"].self_ms == pytest.approx(
        s["outer"].total_ms - s["inner"].total_ms)
    assert s["inner"].self_ms == pytest.approx(
        s["inner"].total_ms - s["leaf"].total_ms)
    assert s["outer"].self_ms >= 2.0 and s["leaf"].self_ms >= 6.0
    assert s["inner"].max_ms <= s["inner"].total_ms
    assert s["inner"].max_ms >= s["inner"].total_ms / 2


def test_counters_and_reset():
    rec = Recorder()
    rec.count("rows", 3)
    rec.count("rows", 5)
    rec.count("hits")
    with rec.span("a"):
        pass
    assert rec.counters == {"rows": 8, "hits": 1}
    rec.reset()
    assert rec.counters == {} and rec.spans() == {}


def test_spans_record_with_no_profiler(ds, graph):
    """A search outside serve_loop: the engine's own spans, one each per
    bucket dispatch; its host time is padding through the fetch less the
    device wait."""
    eng = SearchEngine(graph, min_bucket=8, max_bucket=16)
    eng.warmup([8])
    eng.search(ds.queries[:3])
    s = eng.stats().spans
    names = {"kbest.pad", "kbest.enqueue", "kbest.device_wait",
             "kbest.fetch"}
    assert set(s) == names
    assert all(s[n].count == 1 and s[n].self_ms == s[n].total_ms
               for n in names)
    st = eng.stats()
    # the dispatch latency: the jit call through block_until_ready
    assert st.lat_p50_ms >= s["kbest.enqueue"].total_ms \
        + s["kbest.device_wait"].total_ms
    host = sum(s[n].total_ms for n in names - {"kbest.device_wait"})
    assert host <= st.host_p50_ms < host + 50.0
    eng.reset_stats()
    st = eng.stats()
    assert st.spans == {} and st.n_queries == st.rows_padded == 0


def test_serve_loop_spans_on_the_engines(ds, graph, ivf):
    """serve_loop's span goes to the first engine's recorder, each
    dispatch span to its own engine's, around that engine's spans."""
    eg = SearchEngine(graph, min_bucket=8, max_bucket=16, name="g")
    ei = SearchEngine(ivf, min_bucket=8, max_bucket=16, name="i")
    serve_loop({"g": eg, "i": ei},
               [Request(queries=ds.queries[:3], engine="g"),
                Request(queries=ds.queries[3:5], engine="i"),
                Request(queries=ds.queries[5:9], engine="g")])
    sg, si = eg.stats().spans, ei.stats().spans
    assert sg["kbest.serve_loop"].count == 1 and "kbest.serve_loop" not in si
    assert sg["kbest.dispatch"].count == 2 and si["kbest.dispatch"].count == 1
    assert sg["kbest.pad"].count == 2 and si["kbest.pad"].count == 1
    # self time: the span less the spans of its recorder directly inside
    assert sg["kbest.serve_loop"].self_ms == pytest.approx(
        sg["kbest.serve_loop"].total_ms - sg["kbest.dispatch"].total_ms)
    for s in (sg, si):
        children = sum(s[n].total_ms for n in (
            "kbest.pad", "kbest.enqueue", "kbest.device_wait",
            "kbest.fetch"))
        assert s["kbest.dispatch"].self_ms == pytest.approx(
            s["kbest.dispatch"].total_ms - children)


def test_dispatch_time_of_a_stand_in_search(ds, graph):
    """The scheduler times each engine call in its kbest.dispatch span, so
    a search put in the engine's place is timed like the engine's own."""
    eng = SearchEngine(graph, min_bucket=8, max_bucket=16)
    real = eng.search

    def slow(queries, k=None, search_cfg=None, gt_ids=None):
        _sleep_ms(20)
        return real(queries, k=k, search_cfg=search_cfg, gt_ids=gt_ids)

    eng.search = slow
    rep = serve_loop(eng, [Request(queries=ds.queries[:3])])
    assert rep.results[0].latency_ms >= 20.0
    assert rep.results[0].latency_ms == pytest.approx(
        eng.stats().spans["kbest.dispatch"].total_ms)


# ------------------------------------------------------ engine counters
def test_pad_share_one_short_request(ds, graph):
    eng = SearchEngine(graph, min_bucket=8, max_bucket=16)
    serve_loop(eng, [Request(queries=ds.queries[:3])])
    st = eng.stats()
    assert (st.n_queries, st.rows_padded) == (3, 5)
    assert st.rows_padded / (st.n_queries + st.rows_padded) == 5 / 8


def test_pad_share_coalesced_to_a_full_bucket(ds, graph):
    eng = SearchEngine(graph, min_bucket=8, max_bucket=16)
    rep = serve_loop(eng, [Request(queries=ds.queries[:3]),
                           Request(queries=ds.queries[3:8])])
    st = eng.stats()
    assert rep.n_dispatches == 1
    assert (st.n_queries, st.rows_padded) == (8, 0)


def test_lockstep_counters_match_search_stats(ds, graph):
    """hops / lane_slots is SearchStats' n_hops over iters x live lanes x
    beam width, computed by hand from an unpadded search."""
    eng = SearchEngine(graph, min_bucket=8, max_bucket=16)
    parts = [ds.queries[:3], ds.queries[3:12]]
    want_hops = want_slots = want_dist = want_et = want_iters = 0
    for q in parts:
        eng.search(q)
        _, _, st = graph.search(q, with_stats=True)
        want_hops += int(np.asarray(st.n_hops).sum())
        want_iters += int(st.iters)
        want_slots += int(st.iters) * q.shape[0] * 2
        want_dist += int(np.asarray(st.n_dist).sum())
        want_et += int(np.asarray(st.early_terminated).sum())
    st = eng.stats()
    assert (st.hops, st.iters, st.lane_slots) == (want_hops, want_iters,
                                                  want_slots)
    assert st.dists_per_query * st.n_queries == pytest.approx(want_dist)
    assert st.et_fire_rate * st.n_queries == pytest.approx(want_et)
    assert 0 < st.hops / st.lane_slots < 1


def test_ivf_counters(ds, ivf):
    eng = SearchEngine(ivf, min_bucket=8, max_bucket=16)
    eng.search(ds.queries[:5])
    _, _, st = ivf.search(ds.queries[:5], with_stats=True)
    got = eng.stats()
    assert got.iters == 0 and got.lane_slots == 0
    assert got.hops == int(np.asarray(st.n_hops).sum()) == 5 * 4
    assert got.dists_per_query * 5 == int(np.asarray(st.n_dist).sum())


def test_one_batched_fetch_per_dispatch(ds, graph, monkeypatch):
    eng = SearchEngine(graph, min_bucket=8, max_bucket=8)
    real = jax.device_get
    calls = []

    def spy(x):
        calls.append(len(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    eng.search(ds.queries[:12])                 # two buckets of 8
    assert calls == [3, 3]                      # dists, ids, counters


# ------------------------------------------------- spans in a trace
def _host_events(trace_dir, prefix):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    prof = jax.profiler.ProfileData.from_file(path)
    return [(ev.start_ns, ev.name, dict(ev.stats))
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def test_serve_loop_dispatch_spans_in_a_trace(ds, graph, tmp_path):
    eng = SearchEngine(graph, min_bucket=8, max_bucket=8)
    eng.warmup()
    reqs = [Request(queries=ds.queries[:3]), Request(queries=ds.queries[3:8]),
            Request(queries=ds.queries[8:14])]
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = serve_loop(eng, reqs)
    finally:
        jax.profiler.stop_trace()
    assert rep.n_dispatches == 2                # [3 + 5], [6]
    ev = sorted(_host_events(tmp_path, "kbest."))
    loops = [a for _, n, a in ev if n == "kbest.serve_loop"]
    assert [a["n_requests"] for a in loops] == [3]
    disp = [a for _, n, a in ev if n == "kbest.dispatch"]
    assert [(a["rows"], a["bucket"], a["first_request_id"],
             a["n_requests"]) for a in disp] == [(8, 8, 0, 2), (6, 8, 2, 1)]
    assert all(a["engine"] == "default" for a in disp)
    names = [n for _, n, _ in ev]
    for child in ("kbest.pad", "kbest.enqueue", "kbest.device_wait",
                  "kbest.fetch"):
        assert names.count(child) == 2
    assert eng.stats().spans["kbest.dispatch"].count == 2


# ------------------------------------------------ the compiled programs
@pytest.mark.parametrize("family,scopes", [
    ("graph", {"kbest.seed", "kbest.expand", "kbest.queue"}),
    ("ivf", {"kbest.probe", "kbest.lut", "kbest.scan", "kbest.rerank"}),
])
def test_compiled_text_names_the_program_and_its_scopes(ds, graph, ivf,
                                                        family, scopes):
    eng = SearchEngine(graph if family == "graph" else ivf, min_bucket=8,
                       max_bucket=16)
    eng.warmup([8])
    before = (eng.n_traces, eng.cache_hits, eng.cache_misses)
    hlo = eng.compiled_text(8)
    assert hlo.startswith("HloModule jit_kbest_search_b8,")
    assert set(re.findall(r"/(kbest\.\w+)", hlo)) >= scopes
    assert (eng.n_traces, eng.cache_hits, eng.cache_misses) == before
    with pytest.raises(KeyError):
        eng.compiled_text(16)                   # not warmed
