"""The telemetry subsystem (``repro.obs``): taps are provably free when off
(bit-for-bit outputs, zero new compiles), faithful when on (tap series ==
the engine's own per-epoch metrics), the compile-cache accounting tracks
hits/misses/evictions, run records round-trip with full provenance, and the
scoreboard renders from records alone."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ExperimentSpec, run
from repro.core import experiment as X
from repro.core import gt_drl
from repro.core import schedulers as SCH
from repro.core.force_directed import FDConfig
from repro.dcsim import env as E

ENV = E.build_env(4, seed=0)
FD_CFG = FDConfig(iters=40)
SPEC = ExperimentSpec(technique="fd", objective="carbon", hours=4, cfg=FD_CFG)


# ---------------------------------------------------------------------------
# the zero-cost-when-off contract (the tentpole's acceptance criterion)
# ---------------------------------------------------------------------------

def test_taps_off_is_bit_identical_and_compiles_nothing_new():
    """Running with taps disabled after a tapped run must (a) reproduce the
    taps-off totals bit-for-bit and (b) add zero compiled artifacts — the
    tapped engine is a SEPARATE cache entry, not a mutation of the silent
    one."""
    off = SPEC.replace(taps=())
    base = run(off, ENV)

    on = SPEC.replace(taps=("engine/hour",))
    with obs.capture("engine/hour") as buf:
        tapped = run(on, ENV)
    assert len(buf.events) == SPEC.hours  # one event per epoch

    key_off = X._engine_key(off)
    st0 = obs.engine_stat(key_off)
    again = run(off, ENV)
    st1 = obs.engine_stat(key_off)
    assert st1["misses"] == st0["misses"]     # zero new compiles, asserted
    assert st1["hits"] == st0["hits"] + 1     # via the obs ledger
    for k, v in base["totals"].items():
        assert again["totals"][k] == v        # bit-for-bit, not allclose
        np.testing.assert_allclose(tapped["totals"][k], v, rtol=1e-6)


def test_tapped_and_untapped_artifacts_coexist_under_distinct_keys():
    key_off = X._engine_key(SPEC.replace(taps=()))
    key_on = X._engine_key(SPEC.replace(taps=("engine/hour",)))
    assert key_off != key_on
    stats = obs.cache_stats()
    assert obs.engine_key_str(key_off) in stats["engines"]
    assert obs.engine_key_str(key_on) in stats["engines"]
    assert stats["engines"][obs.engine_key_str(key_on)]["dispatches"] >= 1


def test_tap_series_equals_engine_per_epoch_exactly():
    """The streamed engine/hour values ARE the engine's metrics — same
    arrays, routed out mid-scan — so the series matches per_epoch exactly."""
    spec = SPEC.replace(taps=("engine/hour",))
    with obs.capture("engine/hour") as buf:
        res = run(spec, ENV)
    for k in ("carbon_kg", "cost_usd", "sla_miss_cost_usd"):
        series = buf.series("engine/hour", k)
        expected = [row[k] for row in res["per_epoch"]]
        np.testing.assert_array_equal(series, np.asarray(expected))
    taus = buf.series("engine/hour", "tau")
    np.testing.assert_array_equal(taus, np.arange(spec.hours))


def test_shard_map_engine_rejects_taps():
    spec = ExperimentSpec(technique="fd", engine="batched", hours=2,
                          cfg=FD_CFG, taps=("engine/hour",))
    with pytest.raises(ValueError, match="shard"):
        run(spec, [ENV, ENV], shard=True)


# ---------------------------------------------------------------------------
# solver-trace taps
# ---------------------------------------------------------------------------

def test_nash_residual_tap_streams_finite_nonnegative_values():
    spec = SPEC.replace(hours=3, taps=("game/nash_residual",))
    with obs.capture() as buf:
        run(spec, ENV)
    res = buf.series("game/nash_residual", "residual")
    assert res.shape == (3,)
    assert np.all(np.isfinite(res)) and np.all(res >= 0.0)
    # the probe is only in the tapped artifact; taps-off streams nothing
    with obs.capture() as buf2:
        run(SPEC.replace(hours=3, taps=()), ENV)
    assert buf2.events == []


def test_gt_drl_taps_stream_round_and_ppo_diagnostics():
    from repro.core.ppo import PPOConfig
    cfg = gt_drl.GTDRLConfig(
        ppo=PPOConfig(horizon=2, episodes=4, iters=1, update_epochs=1),
        rounds=2, polish_steps=2, pretrain_iters=2)
    spec = ExperimentSpec(technique="gt-drl", hours=2, cfg=cfg,
                          taps=("gt_drl/*",))
    with obs.capture() as buf:
        run(spec, ENV)
    counts = buf.counts()
    i = E.num_players(ENV)
    assert counts["gt_drl/round"] == spec.hours * cfg.rounds
    assert counts["gt_drl/ppo"] == spec.hours * cfg.rounds * i
    deltas = buf.series("gt_drl/round", "delta")
    assert np.all(np.isfinite(deltas))
    losses = buf.series("gt_drl/ppo", "actor_loss")
    assert np.all(np.isfinite(losses))


def test_tap_pattern_matching_prefix_and_wildcard():
    assert obs.tap_mod._matches("engine/hour", frozenset(["engine/*"]))
    assert obs.tap_mod._matches("engine/hour", frozenset(["*"]))
    assert obs.tap_mod._matches("engine/hour", frozenset(["engine/hour"]))
    assert not obs.tap_mod._matches("engine/hour", frozenset(["gt_drl/*"]))
    assert not obs.tap_mod._matches("engine/hour", frozenset())


def test_ambient_taps_context_drives_spec_default():
    spec = SPEC.replace(hours=2)  # taps=None -> ambient
    assert spec.effective_taps() == frozenset()
    with obs.taps("engine/*"):
        assert spec.effective_taps() == frozenset({"engine/*"})
        with obs.capture("engine/hour") as buf:
            run(spec, ENV)
        assert len(buf.events) == 2
    assert spec.effective_taps() == frozenset()


# ---------------------------------------------------------------------------
# spans + cache accounting
# ---------------------------------------------------------------------------

def test_span_records_wall_time_into_the_stream():
    with obs.span("test/region", tag=1) as s:
        sum(range(1000))
    assert s.seconds > 0.0
    got = obs.all_spans("test/region")
    assert got and got[-1] is s and got[-1].meta == {"tag": 1}


def test_bench_timer_is_an_obs_span():
    from benchmarks.common import Timer
    with Timer() as tm:
        sum(range(1000))
    assert isinstance(tm, obs.Span) and tm.seconds > 0.0
    assert obs.all_spans("bench")[-1] is tm


def test_span_tree_carries_request_parent_and_self_time():
    with obs.request("api.test") as root:
        with obs.span("a") as a:
            with obs.span("b") as b:
                time.sleep(0.002)
            obs.count("rows", 2)
        with obs.span("a") as a2:
            obs.count("rows")
    assert root.parent is None and root.request == root.id
    assert (a.parent, b.parent, a2.parent) == (root.id, a.id, root.id)
    assert {a.request, b.request, a2.request} == {root.id}
    assert root.start <= a.start <= b.start <= b.end <= a.end <= a2.start
    req = obs.requests(last=1)[0]
    assert (req.id, req.name) == (root.id, "api.test")
    assert req.counters == {"rows": 3}
    assert req.spans["b"] == pytest.approx(b.seconds)
    assert req.spans["a"] == pytest.approx(a.seconds - b.seconds + a2.seconds)
    assert req.self_s == pytest.approx(root.seconds - a.seconds - a2.seconds)
    assert req.self_s + sum(req.spans.values()) == pytest.approx(root.seconds)
    # outside any request a span has none, and counters go nowhere
    with obs.span("free") as free:
        obs.count("rows")
    assert free.request is None and free.parent is None
    assert obs.requests(last=1)[0] is req


def test_one_request_per_outermost_public_call():
    from repro.core import sweep
    last = obs.requests(last=1)
    before = last[0].id if last else 0
    run(SPEC, ENV)
    with obs.request("api.outer"):
        run(SPEC, ENV)    # nested public calls join the outer request
        run(SPEC, ENV)
    sweep(SPEC, {"wan_degradation": (1.0, 2.0)}, base_env=ENV)
    reqs = [r for r in obs.requests() if r.id > before]
    assert [r.name for r in reqs] == ["api.run", "api.outer", "api.sweep"]
    assert reqs[1].counters["dispatches"] == 2
    assert {"engine.inputs", "engine.dispatch", "engine.fetch",
            "engine.format"} <= set(reqs[0].spans)
    assert {"sweep.grid", "scenario.wan_degradation", "sweep.stack",
            "engine.inputs", "engine.dispatch", "engine.fetch",
            "engine.format"} <= set(reqs[2].spans)
    roots = [s for s in obs.all_spans() if s.id in {r.id for r in reqs}]
    assert [s.parent for s in roots] == [None] * 3


def test_requests_last_n_carry_the_calls_counters():
    from repro.core import sweep
    one = run(SPEC, ENV)
    batch = run(SPEC.replace(engine="batched", seeds=(0, 1, 2)),
                [ENV, ENV, ENV])
    grid = sweep(SPEC, {"wan_degradation": (1.0, 2.0),
                        "sla_tighten": (1.0, 0.8)}, base_env=ENV)
    reqs = obs.requests(last=3)
    assert [r.name for r in reqs] == ["api.run", "api.run", "api.sweep"]
    h = SPEC.hours
    assert [r.counters["rows"] for r in reqs] == [1, 3, 4]
    assert [r.counters["fleet_hours"] for r in reqs] == [h, 3 * h, 4 * h]
    assert [r.counters["dispatches"] for r in reqs] == [1, 1, 1]
    assert [r.counters["fetches"] for r in reqs] == [
        len(one["per_epoch"][0]) - 1, len(batch["per_epoch"]),
        len(grid["results"]["fd"]["per_epoch"])]
    assert obs.requests(last=0) == []
    assert obs.requests(last=2) == reqs[1:]


def test_call_with_timeout_keeps_spans_under_the_callers_request(tmp_path):
    from repro.core import sweep
    from repro.faults.resume import call_with_timeout

    def work():
        with obs.span("worker") as w:
            obs.count("rows")
        return w

    with obs.request("api.test") as root:
        with obs.span("sweep.chunk") as chunk:
            w = call_with_timeout(work, 30.0)
    assert (w.parent, w.request) == (chunk.id, root.id)
    req = obs.requests(last=1)[0]
    assert req.counters == {"rows": 1} and "worker" in req.spans

    # a journaled sweep with a chunk timeout: engine spans stay under
    # their chunk, in the sweep's request
    sweep(SPEC, {"wan_degradation": (1.0, 2.0, 3.0)}, base_env=ENV,
          resume_dir=str(tmp_path / "journal"), chunk_points=2,
          point_timeout_s=120.0)
    req = obs.requests(last=1)[0]
    assert req.name == "api.sweep"
    assert req.counters["dispatches"] == 2 and req.counters["rows"] == 3
    mine = [s for s in obs.all_spans() if s.request == req.id]
    chunks = {s.id for s in mine if s.name == "sweep.chunk"}
    dispatch = [s for s in mine if s.name == "engine.dispatch"]
    assert len(chunks) == 2 and len(dispatch) == 2
    assert {s.parent for s in dispatch} == chunks


def test_worker_threads_lose_no_counts_or_span_time():
    import contextvars
    import sys
    import threading

    n = 2 * (os.cpu_count() or 1)     # more threads than cores
    per = 3000 // n                   # all their spans stay in the ring

    def work():
        for _ in range(per):
            with obs.span("w"):
                obs.count("rows")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.request("api.test") as root:
            threads = [threading.Thread(target=contextvars.copy_context().run,
                                        args=(work,))
                       for _ in range(n)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    req = obs.requests(last=1)[0]
    assert req.id == root.id
    assert req.counters["rows"] == per * n
    mine = [s for s in obs.all_spans("w") if s.request == root.id]
    assert req.spans["w"] == pytest.approx(sum(s.seconds for s in mine))
    assert req.self_s == pytest.approx(root.seconds - req.spans["w"])


def test_span_lands_on_the_host_plane_of_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    with obs.profile("spans", logdir=str(tmp_path)) as p:
        with jax.profiler.TraceAnnotation("test.outer"):
            with obs.span("test.inner"):
                jnp.ones(4).block_until_ready()
    path = [os.path.join(d, f) for d, _, fs in os.walk(p) for f in fs
            if f.endswith(".xplane.pb")][0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events[ev.name] = (ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
    assert "test.inner" in events and "test.outer" in events
    (o0, o1), (i0, i1) = events["test.outer"], events["test.inner"]
    assert o0 <= i0 <= i1 <= o1


def test_named_scopes_reach_the_compiled_hlo():
    from repro import faults as FL
    key, state0 = X._day_inputs(ENV, "fd", "carbon", 0, True, FD_CFG)
    peak0 = jnp.zeros((E.num_dcs(ENV),))
    plain = X._day_core("fd", "carbon", 2, FD_CFG)
    txt = jax.jit(plain).lower(ENV, key, peak0, state0).compile().as_text()
    assert "solver/" in txt and "simulate/" in txt
    assert "failover/" not in txt
    faulted = X._day_core("fd", "carbon", 2, FD_CFG, faulted=True)
    trace = FL.random_trace(ENV, seed=0)
    txt = jax.jit(faulted).lower(ENV, key, peak0, state0,
                                 trace).compile().as_text()
    assert "solver/" in txt and "failover/" in txt and "guard/" in txt


def test_cache_stats_dispatch_accounting():
    run(SPEC, ENV)
    st = obs.engine_stat(X._engine_key(SPEC))
    assert st["dispatches"] >= 1
    assert st["dispatch_s"] >= st["last_dispatch_s"] > 0.0
    assert st["first_dispatch_s"] > 0.0  # ≈ trace + XLA compile + run
    totals = obs.cache_stats()
    assert totals["misses"] >= 1 and totals["live_keys"] >= 1


def test_stats_single_run_stderr_is_zero_not_nan():
    """Regression: n=1 must report stderr 0.0 — the ddof=1 std is NaN at a
    single sample and would poison every downstream mean±stderr table."""
    out = SCH._stats([42.0], [[1.0, 2.0, 3.0]])
    assert out["mean"] == 42.0
    assert out["stderr"] == 0.0 and not np.isnan(out["stderr"])
    multi = SCH._stats([40.0, 44.0], [[1.0], [3.0]])
    assert multi["stderr"] > 0.0


# ---------------------------------------------------------------------------
# run records + the scoreboard
# ---------------------------------------------------------------------------

def test_run_record_roundtrip_with_provenance(tmp_path):
    path = str(tmp_path / "records.jsonl")
    res = run(SPEC, ENV, record=path)
    recs = obs.load_records(path)
    assert len(recs) == 1
    rec = recs[0]
    for field in ("git_sha", "jax_version", "backend", "device_kind",
                  "device_count", "cpu_count", "timestamp_utc"):
        assert field in rec, field
    assert rec["kind"] == "run"
    assert rec["spec"]["technique"] == "fd" and rec["spec"]["hours"] == 4
    assert rec["spec_key"] == obs.spec_key(SPEC)
    assert rec["totals"]["carbon_kg"] == res["totals"]["carbon_kg"]
    assert len(rec["curves"]["carbon_kg"]) == SPEC.hours
    assert rec["engine_spans"]["dispatches"] >= 1


def test_compare_techniques_emits_one_record_per_technique(tmp_path):
    path = str(tmp_path / "compare.jsonl")
    out = SCH.compare_techniques(
        [ENV], ("fd",), "carbon", hours=3, cfg_overrides={"fd": FD_CFG},
        record=path)
    recs = obs.load_records(path)
    assert len(recs) == 1 and recs[0]["kind"] == "compare"
    assert recs[0]["mean"] == out["fd"]["mean"]
    assert recs[0]["curves"]["carbon_kg"] == out["fd"]["curve_mean"]
    assert recs[0]["runs"] == 1 and recs[0]["stderr"] == 0.0


def test_sweep_emits_records_with_grid_labels(tmp_path):
    from repro.core import sweep
    path = str(tmp_path / "sweep.jsonl")
    spec = ExperimentSpec(technique="fd", objective="cost_sla",
                          engine="batched", hours=2, cfg=FD_CFG)
    sweep(spec, {"wan_degradation": (1.0, 2.0)}, base_env=ENV, record=path)
    recs = obs.load_records(path)
    assert len(recs) == 1 and recs[0]["kind"] == "sweep"
    assert len(recs[0]["labels"]) == 2


def test_report_renders_ranked_scoreboard(tmp_path):
    path = str(tmp_path / "records.jsonl")
    for t in ("fd", "ga"):
        spec = ExperimentSpec(technique=t, hours=3,
                              cfg=FD_CFG if t == "fd" else None)
        run(spec, ENV, record=path)
    md = obs.report(obs.load_records(path), title="test board")
    assert "test board" in md and "fd" in md and "ga" in md
    assert "carbon_kg" in md
    assert any(c in md for c in "▁▂▃▄▅▆▇█")  # convergence sparklines
    # one header + one row per technique in the carbon table
    rows = [ln for ln in md.splitlines()
            if ln.startswith("| ") and "technique" not in ln]
    assert len(rows) == 2
    # ranked: the lower-carbon technique's row comes first
    carbons = [float(ln.split("|")[4]) for ln in rows]
    assert carbons == sorted(carbons)


def test_sparkline_shapes():
    assert obs.sparkline([]) == ""
    assert len(obs.sparkline([1.0])) == 1
    s = obs.sparkline(list(range(32)), width=16)
    assert len(s) == 16 and s[0] == "▁" and s[-1] == "█"
    assert set(obs.sparkline([5.0, 5.0, 5.0])) <= set("▁▂▃▄▅▆▇█")


def test_bench_json_meta_carries_provenance():
    from benchmarks.run import rows_to_json
    payload = rows_to_json(["header", "x/y,12,d=1"], ("engine",), 1.0)
    meta = payload["meta"]
    for field in ("git_sha", "jax_version", "device_kind", "cpu_count"):
        assert field in meta, field
    assert payload["rows"] == [
        {"name": "x/y", "us_per_call": 12.0, "derived": "d=1"}]


def test_profile_writes_a_trace_or_degrades_gracefully(tmp_path):
    with obs.profile("unit", logdir=str(tmp_path)) as p:
        jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    assert os.path.isdir(p)
    traces = [f for _, _, fs in os.walk(p) for f in fs
              if f.endswith(".xplane.pb")]
    assert traces, f"no profiler trace written under {p}"


def test_profile_raises_when_the_profiler_cannot_start(tmp_path, monkeypatch):
    def refuse(path):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler busy"):
        with obs.profile("unit", logdir=str(tmp_path)):
            pass
