"""Observability layer (repro.obs): resolve-helper precedence, trace
schema golden keys, the disabled-spec bitwise no-op on both round
engines, compile-tracker recompile detection, measured pipeline
overlap, sweep scheduling spans surviving preemption + manifest
reload, and the unified CLI metrics schema."""
import dataclasses
import glob
import json
import os
import time

import numpy as np
import jax
import pytest

from repro.configs import SMOKE_UNET, register_config
from repro.configs.base import FLConfig
from repro.core.aggregation import weighted_sum
from repro.core.hfl import FedPhD
from repro.data import ClientData, shards_per_client
from repro.data.synthetic import DatasetSpec, make_dataset
from repro.experiment import (DataSpec, ExperimentSpec, FakeCluster,
                              K8sExecutor, SweepSpec, register_dataset,
                              run_sweep)
from repro.experiment.cli import (METRICS_SCHEMA, cli_obs_spec,
                                  make_cli_tracer, write_metrics)
from repro.experiment.report import run_scalars
from repro.experiment.resolve import (BACKENDS, KNOBS, knob_source,
                                      resolve_engine, resolve_knob,
                                      resolve_obs, validate_env)
from repro.fl.baselines import FlatTrainer
from repro.fl.client import Client, temp_bytes
from repro.obs.compile_tracker import CompileTracker, cache_size
from repro.obs.metrics import summarize_trace
from repro.obs.spec import ObsSpec
from repro.obs.trace import (COUNTER_KEYS, EVENT_KEYS, META_KEYS, NULL_TRACER,
                             SCHEMA_VERSION, SPAN_KEYS, Tracer, make_tracer)
from repro.optim import adam_init

MICRO_UNET = SMOKE_UNET.replace(name="ddpm-unet-tiny-obs", image_size=8,
                                base_channels=8, channel_mults=(1,),
                                num_res_blocks=1, attn_resolutions=())
MICRO_DATA = DatasetSpec("tiny-obs", num_classes=4, image_size=8,
                         samples_per_class=32)

FL = FLConfig(num_clients=4, num_edges=1, local_epochs=1, edge_agg_every=1,
              cloud_agg_every=2, rounds=3, sh_a=1000.0)


def make_clients(n=4, batch_size=8):
    images, labels = make_dataset(MICRO_DATA, seed=0)
    parts = shards_per_client(labels, num_clients=n, classes_per_client=1,
                              seed=0)
    return [Client(i, ClientData(images[p], labels[p],
                                 batch_size=batch_size, seed=i),
                   MICRO_DATA.num_classes) for i, p in enumerate(parts)]


def read_lines(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# -- satellite: the one $FEDPHD_* resolution code path ------------------------

def test_resolve_precedence_matrix(monkeypatch):
    """explicit > $FEDPHD_<KNOB> > default, for every knob; '' means
    unset at BOTH levels; unknown values raise at resolution time."""
    for name, knob in KNOBS.items():
        monkeypatch.delenv(knob.env, raising=False)
        assert resolve_knob(name) == knob.default
        assert knob_source(name) == "default"
        env_val = next(c for c in knob.choices if c != knob.default)
        explicit = knob.default
        monkeypatch.setenv(knob.env, env_val)
        assert resolve_knob(name) == env_val
        assert knob_source(name) == "env"
        # explicit beats env even when explicit happens to be the default
        assert resolve_knob(name, explicit) == explicit
        assert knob_source(name, explicit) == "explicit"
        # '' is "not set" on both legs
        monkeypatch.setenv(knob.env, "")
        assert resolve_knob(name, "") == knob.default
        monkeypatch.setenv(knob.env, env_val)
        assert resolve_knob(name, "") == env_val
        # typos fail fast, never fall back silently
        with pytest.raises(ValueError, match=f"unknown {name}"):
            resolve_knob(name, "bogus")
        monkeypatch.setenv(knob.env, "bogus")
        with pytest.raises(ValueError, match="from env"):
            resolve_knob(name)
        with pytest.raises(RuntimeError, match=knob.env):
            validate_env(name)
        monkeypatch.delenv(knob.env, raising=False)
        assert validate_env(name) is None


def test_resolve_engine_strictness(monkeypatch):
    monkeypatch.delenv("FEDPHD_ENGINE", raising=False)
    assert resolve_engine(None) == ("auto", False)
    assert resolve_engine("vectorized") == ("vectorized", True)
    monkeypatch.setenv("FEDPHD_ENGINE", "sequential")
    # env-selected engines are non-strict (matrix legs stay green on
    # ragged fixtures); explicit choices are strict
    assert resolve_engine(None) == ("sequential", False)
    assert resolve_engine("vectorized") == ("vectorized", True)


def test_resolve_obs_aliases(monkeypatch):
    for raw, want in (("1", True), ("true", True), ("YES", True),
                      ("on", True), ("0", False), ("false", False),
                      ("no", False), ("off", False)):
        monkeypatch.setenv("FEDPHD_OBS", raw)
        assert resolve_obs() is want
    monkeypatch.delenv("FEDPHD_OBS", raising=False)
    assert resolve_obs() is False
    assert resolve_obs("on") is True


def test_obs_spec_resolution_and_roundtrip(monkeypatch):
    monkeypatch.delenv("FEDPHD_OBS", raising=False)
    assert ObsSpec().resolved_enabled is False
    assert ObsSpec(enabled=True).resolved_enabled is True
    monkeypatch.setenv("FEDPHD_OBS", "on")
    assert ObsSpec().resolved_enabled is True          # env leg
    assert ObsSpec(enabled=False).resolved_enabled is False  # explicit wins
    with pytest.raises(ValueError, match="flush_every"):
        ObsSpec(flush_every=0)
    spec = ObsSpec(enabled=True, trace="t.jsonl", flush_every=8,
                   compile_tracking=False)
    assert ObsSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec
    # unknown keys from future manifests are dropped, not fatal
    assert ObsSpec.from_dict({"enabled": True, "shiny": 1}) \
        == ObsSpec(enabled=True)


def test_experiment_spec_carries_obs():
    spec = ExperimentSpec(name="obs-rt", method="fedavg", model="m",
                          fl=FL, data=DataSpec(dataset="d", batch_size=8),
                          obs=ObsSpec(enabled=True, trace="x.jsonl"))
    back = ExperimentSpec.from_dict(json.loads(spec.to_json()))
    assert back.obs == spec.obs
    # obs.* is addressable as a sweep axis like fl.* / fault.*
    grid = SweepSpec(name="g", base=spec,
                     axes={"obs.enabled": [False, True], "seed": [0]})
    runs = grid.expand()
    assert {run.overrides["obs.enabled"] for run in runs} == {False, True}
    assert {run.spec.obs.enabled for run in runs} == {False, True}


# -- trace schema -------------------------------------------------------------

def test_trace_schema_golden_keys(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = Tracer(path)
    with tr.span("round/dispatch", round=1):
        pass
    tr.record_span("serve/tick", 1.0, 2.5, active=3)
    tr.event("fault/draw", round=1, dropped=0)
    tr.counter("compile/step", 1, unexpected=0)
    tr.close()
    lines = read_lines(path)
    golden = {"meta": META_KEYS, "span": SPAN_KEYS,
              "event": EVENT_KEYS, "counter": COUNTER_KEYS}
    assert [ln["ev"] for ln in lines] == ["meta", "span", "span",
                                          "event", "counter"]
    for ln in lines:
        assert set(ln) == set(golden[ln["ev"]])
    assert lines[0]["schema"] == SCHEMA_VERSION
    assert lines[2]["dur_s"] == pytest.approx(1.5)
    # reopening appends a fresh meta line: sessions delimit in-band,
    # so perf_counter stamps are never compared across processes
    Tracer(path).close()
    metas = [ln for ln in read_lines(path) if ln["ev"] == "meta"]
    assert len(metas) == 2


def test_make_tracer_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv("FEDPHD_OBS", raising=False)
    assert make_tracer(ObsSpec()) is NULL_TRACER
    assert make_tracer(None) is NULL_TRACER
    monkeypatch.setenv("FEDPHD_OBS", "on")
    tr = make_tracer(ObsSpec(), default_path=str(tmp_path / "a.jsonl"))
    assert tr.enabled and tr.path.endswith("a.jsonl")
    tr.close()
    # spec path beats the caller default
    tr = make_tracer(ObsSpec(trace=str(tmp_path / "b.jsonl")),
                     default_path=str(tmp_path / "a.jsonl"))
    assert tr.path.endswith("b.jsonl")
    tr.close()


# -- the hard invariant: obs disabled is a bitwise no-op ---------------------

@pytest.mark.parametrize("engine", ["sequential", "vectorized"])
def test_disabled_obs_bitwise_noop_fedphd(engine, tmp_path):
    """Same seed, with and without a bound tracer: parameters bitwise
    identical, histories identical — tracing never touches RNG or
    numerics on either engine."""
    plain = FedPhD(MICRO_UNET, FL, make_clients(), rng_seed=0,
                   engine=engine, prune=False)
    plain.run(2)
    tracer = Tracer(str(tmp_path / f"{engine}.jsonl"))
    traced = FedPhD(MICRO_UNET, FL, make_clients(), rng_seed=0,
                    engine=engine, prune=False, tracer=tracer)
    traced.run(2)
    tracer.close()
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(traced.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert [h.to_dict() for h in plain.history] \
        == [h.to_dict() for h in traced.history]
    # and the traced leg actually emitted round phase spans (the
    # sequential reference loop syncs per batch, so it gets only the
    # one dispatch span; loss_sync exists on the deferred-sync engine)
    names = {ln["name"] for ln in read_lines(tracer.path)
             if ln["ev"] == "span"}
    want = {"round/dispatch"} if engine == "sequential" \
        else {"round/dispatch", "round/loss_sync"}
    assert want <= names


def _inside(child, parent):
    return parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"]


def test_sequential_round_client_and_edge_spans(tmp_path):
    """A traced sequential round: each client's training, each local
    step with its batch copy, dispatch and loss sync, and each edge's
    aggregation are spans, with their attributes, nested inside the
    round's dispatch span.  The client spans carry the step's
    rematerialization setting, and the compiled step's temporary bytes
    are one counter.  The aggregation spans carry the count of
    compiled member-count programs, which a count already seen does
    not raise."""
    tracer = Tracer(str(tmp_path / "seq.jsonl"))
    clients = make_clients()
    remat = "dots_with_no_batch_dims_saveable"
    tr = FedPhD(MICRO_UNET.replace(remat_policy=remat), FL, clients,
                rng_seed=0, engine="sequential", prune=False, tracer=tracer)
    tr.run_round(1)
    programs = cache_size(weighted_sum)
    split = time.perf_counter()
    tr.run_round(2)
    tracer.close()
    by, by2 = {}, {}
    for ln in read_lines(tracer.path):
        if ln["ev"] == "span":
            (by if ln["t1"] < split else by2).setdefault(
                ln["name"], []).append(ln)
    steps = FL.local_epochs * clients[0].data.steps_per_epoch
    (dispatch,) = by["round/dispatch"]
    trains = by["client/train"]
    assert sorted(t["attrs"]["client"] for t in trains) == [0, 1, 2, 3]
    assert all(t["attrs"]["round"] == 1 and t["attrs"]["edge"] == 0
               and t["attrs"]["remat"] == remat
               and _inside(t, dispatch) for t in trains)
    assert len(by["client/step"]) == len(trains) * steps
    for st in by["client/step"]:
        assert any(_inside(st, t) for t in trains)
        assert st["attrs"] == {"remat": remat}
    (temp,) = [ln for ln in read_lines(tracer.path)
               if ln["ev"] == "counter" and ln["name"] == "step/temp_bytes"]
    assert temp["attrs"] == {"remat": remat}
    batch = {k: jax.numpy.asarray(v)
             for k, v in next(iter(clients[0].data.epoch())).items()}
    assert temp["value"] == temp_bytes(
        tr.step_plain, tr.params, adam_init(tr.params), batch,
        jax.random.PRNGKey(0), {}) > 0
    assert temp["t"] < split
    for name in ("client/h2d", "client/dispatch", "client/loss_sync"):
        assert len(by[name]) == len(trains) * steps, name
        for sp in by[name]:
            assert any(_inside(sp, st) for st in by["client/step"]), name
    (agg,) = by["round/edge_agg"]
    assert agg["attrs"] == {"round": 1, "edge": 0, "clients": 4,
                            "programs": agg["attrs"]["programs"]}
    assert agg["attrs"]["programs"] in (programs - 1, programs)
    assert _inside(agg, dispatch)
    assert all(t["t1"] <= agg["t0"] for t in trains)
    # round 2 aggregates four clients again, then the one edge at the
    # cloud: the repeated count compiled nothing
    (agg2,) = by2["round/edge_agg"]
    (cloud,) = by2["round/cloud_agg"]
    assert agg2["attrs"]["clients"] == 4
    assert agg2["attrs"]["programs"] == programs
    assert cloud["attrs"] == {"round": 2, "programs": programs}


def test_spans_reach_the_profiler_trace(tmp_path):
    """Spans of both tracers, and the enabled tracer's counters, land on
    a host plane of the profiler's own trace, with their attributes,
    whether or not the JSON-lines sink is on."""
    from jax.profiler import ProfileData
    tracer = Tracer(str(tmp_path / "p.jsonl"))
    with jax.profiler.trace(str(tmp_path / "prof")):
        with tracer.span("round/dispatch", round=4):
            with NULL_TRACER.span("client/step", remat="policy"):
                jax.numpy.ones(2).block_until_ready()
            tracer.counter("step/temp_bytes", 7, remat="policy")
    tracer.close()
    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name.split("#")[0]] = (dict(ev.stats), ev)
    assert host["round/dispatch"][0]["round"] == 4
    assert host["client/step"][0]["remat"] == "policy"
    assert (host["step/temp_bytes"][0]["value"],
            host["step/temp_bytes"][0]["remat"]) == (7, "policy")
    outer, inner = host["round/dispatch"][1], host["client/step"][1]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    assert [ln["name"] for ln in read_lines(tracer.path)
            if ln["ev"] == "span"] == ["round/dispatch"]


def test_disabled_obs_bitwise_noop_flat(tmp_path):
    plain = FlatTrainer("fedavg", MICRO_UNET, FL, make_clients(),
                        rng_seed=0, engine="vectorized")
    plain.run(2)
    tracer = Tracer(str(tmp_path / "flat.jsonl"))
    traced = FlatTrainer("fedavg", MICRO_UNET, FL, make_clients(),
                         rng_seed=0, engine="vectorized", tracer=tracer)
    traced.run(2)
    tracer.close()
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(traced.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert [h.to_dict() for h in plain.history] \
        == [h.to_dict() for h in traced.history]


# -- compile tracker ----------------------------------------------------------

def test_compile_tracker_catches_induced_recompile(tmp_path):
    path = str(tmp_path / "c.jsonl")
    tracer = Tracer(path)
    tracker = CompileTracker(tracer)

    @jax.jit
    def f(x):
        return x * 2

    assert tracker.watch("f", f)
    f(np.ones((4,), np.float32))
    assert tracker.check() == 0            # the expected first compile
    assert tracker.compiles() == 1 and tracker.recompiles() == 0
    f(np.ones((8,), np.float32))           # new shape -> a real recompile
    assert tracker.check() == 1
    assert tracker.recompiles() == 1
    # a re-watch is a DECLARED recompile boundary (the trainers re-watch
    # after pruning): the next compile is expected again
    assert tracker.watch("f", f)
    f(np.ones((16,), np.float32))
    assert tracker.check() == 0
    assert tracker.recompiles() == 1
    tracer.close()
    counters = [ln for ln in read_lines(path) if ln["ev"] == "counter"]
    assert [c["attrs"]["unexpected"] for c in counters] == [0, 1, 0]
    assert all(c["name"] == "compile/f" for c in counters)


def test_cache_size_degrades_gracefully():
    assert cache_size(lambda x: x) is None
    tracker = CompileTracker(NULL_TRACER)
    assert tracker.watch("plain", lambda x: x) is False
    assert tracker.check() == 0


# -- trace-derived metrics ----------------------------------------------------

def test_traced_run_overlap_and_zero_recompiles(tmp_path):
    """A pipelined traced run: phase spans per round, a measurable
    overlap window, and zero steady-state recompiles (the jit caches
    only grow at the declared first-compile boundaries)."""
    path = str(tmp_path / "run.jsonl")
    # a config name unique to this test: the round engine is memoized
    # on the full config, so this guarantees a FRESH jit cache — the
    # compile counter must see the expected first compile
    cfg = MICRO_UNET.replace(name="ddpm-unet-tiny-obs-traced")
    tr = FedPhD(cfg, FL, make_clients(), rng_seed=0,
                engine="vectorized", prune=False, tracer=Tracer(path))
    tr.run(3)
    tr._obs.close()
    ts = summarize_trace(path)
    for phase in ("round/host_prep", "round/h2d", "round/dispatch",
                  "round/loss_sync"):
        assert ts["phases"][phase]["n"] >= 3, phase
    assert ts["rounds"] == 3
    assert ts["overlap_ratio"] is not None
    assert 0.0 <= ts["overlap_ratio"] <= 1.0
    assert ts["compiles"] >= 1
    assert ts["recompiles"] == 0


def test_summarize_trace_sessions_split():
    events = [
        {"ev": "meta", "schema": 1, "wall_time": 0.0, "attrs": {}},
        {"ev": "span", "name": "round/dispatch", "t0": 0.0, "t1": 1.0,
         "dur_s": 1.0, "attrs": {"round": 1}},
        {"ev": "span", "name": "round/h2d", "t0": 1.2, "t1": 1.8,
         "dur_s": 0.6, "attrs": {"round": 2}},
        {"ev": "span", "name": "round/loss_sync", "t0": 2.0, "t1": 2.1,
         "dur_s": 0.1, "attrs": {"round": 1}},
        {"ev": "meta", "schema": 1, "wall_time": 9.0, "attrs": {}},
        {"ev": "span", "name": "round/dispatch", "t0": 0.0, "t1": 0.5,
         "dur_s": 0.5, "attrs": {"round": 3}},
    ]
    ts = summarize_trace(events)
    assert ts["sessions"] == 2
    # round 2's h2d (0.6s) hides fully inside round 1's 1.0s window;
    # the second session contributes no window (no loss_sync)
    assert ts["overlap_window_s"] == pytest.approx(1.0)
    assert ts["overlap_hidden_s"] == pytest.approx(0.6)
    assert ts["overlap_ratio"] == pytest.approx(0.6)


# -- sweep scheduling spans ---------------------------------------------------

register_config("ddpm-unet-tiny-obs", MICRO_UNET, overwrite=True)
register_dataset("tiny-obs", MICRO_DATA, overwrite=True)

SWEEP_BASE = ExperimentSpec(
    name="obs-sweep-base", method="fedavg", model="ddpm-unet-tiny-obs",
    fl=dataclasses.replace(FL, rounds=2),
    data=DataSpec(dataset="tiny-obs", batch_size=8),
    engine="sequential", prune=False)
SWEEP = SweepSpec(name="obs-sweep", base=SWEEP_BASE,
                  axes={"seed": [0, 1]})


def test_sweep_spans_survive_preemption_and_reload(tmp_path):
    """The executor records queue/attempt/backoff spans into the
    manifest; a preempted attempt surfaces as outcome="preempted", the
    retry as "done" — and the spans survive a manifest reload (the
    kill-and-resume path re-reads sweep.json)."""
    rid = "seed=0"
    exe = K8sExecutor(cluster=FakeCluster(preempt_once={rid: 1}),
                      poll_s=0.0)
    res = run_sweep(SWEEP, str(tmp_path), executor=exe, max_retries=1)
    assert res.complete
    trace = res.manifest["runs"][rid]["trace"]
    outcomes = [s["attrs"]["outcome"] for s in trace
                if s["name"] == "sweep/attempt"]
    assert outcomes == ["preempted", "done"]
    assert any(s["name"] == "sweep/backoff" for s in trace)
    queue = [s for s in trace if s["name"] == "sweep/queue"]
    assert len(queue) == 2                 # initial launch + the retry
    assert all(s["dur_s"] >= 0 for s in trace)
    # epoch stamps: spans are ordered across attempts within one entry
    attempts = [s for s in trace if s["name"] == "sweep/attempt"]
    assert attempts[0]["t1"] <= attempts[1]["t0"]

    # resume on the same out dir: nothing reruns, spans survive
    exe2 = K8sExecutor(cluster=FakeCluster(fail_submits=True), poll_s=0.0)
    res2 = run_sweep(SWEEP, str(tmp_path), executor=exe2)
    assert res2.complete
    assert res2.manifest["runs"][rid]["trace"] == trace


def test_sequential_executor_records_spans(tmp_path):
    res = run_sweep(SWEEP, str(tmp_path))
    for entry in res.manifest["runs"].values():
        names = [s["name"] for s in entry["trace"]]
        assert "sweep/queue" in names and "sweep/attempt" in names
        done = [s for s in entry["trace"] if s["name"] == "sweep/attempt"]
        assert done[-1]["attrs"]["outcome"] == "done"


def test_report_scheduling_scalars():
    entry = {
        "status": "done", "attempts": 2, "wall_s": 5.0,
        "history": [{"loss": 0.5, "comm_gb": 0.1, "params_m": 1.0}],
        "trace": [
            {"ev": "span", "name": "sweep/queue", "t0": 0.0, "t1": 1.0,
             "dur_s": 1.0, "attrs": {"attempt": 0}},
            {"ev": "span", "name": "sweep/attempt", "t0": 1.0, "t1": 3.0,
             "dur_s": 2.0, "attrs": {"outcome": "preempted"}},
            {"ev": "span", "name": "sweep/backoff", "t0": 3.0, "t1": 3.5,
             "dur_s": 0.5, "attrs": {"attempt": 1}},
            {"ev": "span", "name": "sweep/queue", "t0": 3.5, "t1": 4.0,
             "dur_s": 0.5, "attrs": {"attempt": 1}},
            {"ev": "span", "name": "sweep/attempt", "t0": 4.0, "t1": 5.0,
             "dur_s": 1.0, "attrs": {"outcome": "done"}},
        ],
    }
    out = run_scalars(entry)
    assert out["attempts"] == 2.0
    assert out["queue_s"] == pytest.approx(1.5)
    # retry cost = the backoff window + the preempted attempt's wall
    assert out["retry_s"] == pytest.approx(2.5)


# -- unified CLI surface ------------------------------------------------------

def test_cli_obs_spec_forms():
    assert cli_obs_spec(None) == ObsSpec()              # defer to env
    assert cli_obs_spec("") == ObsSpec(enabled=True)    # bare --trace
    assert cli_obs_spec("t.jsonl") \
        == ObsSpec(enabled=True, trace="t.jsonl")       # pinned path


def test_make_cli_tracer(tmp_path, monkeypatch):
    monkeypatch.delenv("FEDPHD_OBS", raising=False)
    assert make_cli_tracer(None).enabled is False
    tr = make_cli_tracer("", default_path=str(tmp_path / "d.jsonl"))
    assert tr.enabled and tr.path.endswith("d.jsonl")
    tr.close()


def test_write_metrics_envelope(tmp_path):
    path = str(tmp_path / "m.json")
    write_metrics(path, "serve", {"images": 8, "compiles": 1})
    with open(path) as f:
        m = json.load(f)
    # envelope keys ADD to the flat metric keys: existing CI assertions
    # like m["images"] keep working across runner and serve
    assert m["schema"] == METRICS_SCHEMA and m["kind"] == "serve"
    assert m["images"] == 8 and m["compiles"] == 1
