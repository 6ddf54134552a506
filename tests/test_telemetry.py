"""Telemetry plane: registry/span semantics, the zero-overhead-off
contract (bit-identical exact digests with telemetry off *and* on),
kernel profiling hooks, exporters (JSONL + Chrome trace), the CLI and
TimeModel calibration.

The two load-bearing tests are the digest-parity pair
(``TestContract``): telemetry off must reproduce the same
``Trace.exact_digest()`` as a plain run, and telemetry *on* must too —
the plane observes, it never perturbs.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

from repro import telemetry as tel
from repro.gnn.train import DistributedTrainer
from repro.graph import generate, partition_graph
from repro.telemetry import (
    Calibration,
    MetricsRegistry,
    TelemetrySession,
    calibrate_from_session,
    calibrate_from_trace,
    fit_alpha_bw,
    provenance,
)
from repro.telemetry.cli import main as tel_main
from repro.telemetry.export import (
    breakdown_rows,
    chrome_trace,
    load_jsonl,
    render_table,
    write_jsonl,
)


@pytest.fixture(autouse=True)
def _no_leaked_session():
    """A test that dies mid-run must not poison the global session."""
    yield
    tel.deactivate()


@pytest.fixture(scope="module")
def parts():
    g = generate("products", seed=0, scale=0.1)
    return partition_graph(g, 4)


COMMON = dict(
    variant="fixed", epochs=2, batch_size=16, fanouts=(3, 5),
    train_model=False, buffer_frac=0.25, interval=4, trace=True,
)
#: COMMON with the GraphSAGE step on: the ``train`` span and its parts.
TRAIN = dict(COMMON, train_model=True, epochs=1, hidden_dim=16)
#: The two vectorized runtimes: staged (host) and device-resident.
RUNTIMES = {"staged": {}, "device": {"device": "jnp"}}
TRAIN_PARTS = ("train.gather", "train.upload", "train.grads", "train.update")


def capture(logdir, fn):
    """Run ``fn`` under a ``jax.profiler`` capture; returns its result and
    the capture's host events as ``(line, name, start_ns, end_ns)``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = [
        (line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]
    return out, events


# ---------------------------------------------------------------------- #
# registry
# ---------------------------------------------------------------------- #
class TestRegistry:
    def test_counter_scalar_and_vector(self):
        reg = MetricsRegistry()
        reg.counter("a").add(2)
        reg.counter("a").add(3)
        assert reg["a"].total == 5.0
        reg.counter("b").add(np.arange(4))
        reg.counter("b").add(np.ones(4))
        np.testing.assert_array_equal(reg["b"].values, [1, 2, 3, 4])
        assert reg["b"].total == 10.0

    def test_counter_shape_fixed_by_first_add(self):
        reg = MetricsRegistry()
        reg.counter("c").add(np.ones(4))
        with pytest.raises(ValueError, match="shape"):
            reg.counter("c").add(np.ones(3))

    def test_counter_preshaped(self):
        reg = MetricsRegistry()
        c = reg.counter("pairwise", shape=(3, 3))
        assert c.values.shape == (3, 3)
        c.add(np.eye(3))
        assert c.total == 3.0

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x").add(1)
        with pytest.raises(ValueError, match="counter"):
            reg.gauge("x")

    def test_gauge_last_write_wins(self):
        reg = MetricsRegistry()
        reg.gauge("g").set(1.0)
        reg.gauge("g").set(7.0)
        assert reg["g"].total == 7.0

    def test_histogram_moments_and_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        h.observe([1.0, 2.0, 3.0, 4.0])
        h.observe(10.0)
        assert h.count == 5
        assert h.sum == 20.0
        assert h.min == 1.0 and h.max == 10.0
        assert h.mean == 4.0
        assert h.percentile(50) == 3.0

    def test_histogram_sample_is_bounded(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        h.cap = 8
        h.observe(np.arange(100, dtype=float))
        assert h.count == 100
        assert len(h._sample) == 8

    def test_summary_shape(self):
        reg = MetricsRegistry()
        reg.counter("a").add(1)
        reg.gauge("b").set(2)
        reg.histogram("c").observe(3)
        s = reg.summary()
        assert set(s) == {"counters", "gauges", "histograms"}
        assert "a" in s["counters"] and "b" in s["gauges"]
        json.dumps(s)  # JSON-safe


# ---------------------------------------------------------------------- #
# spans
# ---------------------------------------------------------------------- #
class TestSpans:
    def test_nesting_depth_and_exclusive_time(self):
        session = TelemetrySession()
        tr = session.tracer
        with tr.span("outer", plane="runtime"):
            with tr.span("inner", plane="engine"):
                pass
        outer = next(s for s in tr.spans if s.name == "outer")
        inner = next(s for s in tr.spans if s.name == "inner")
        assert outer.depth == 0 and inner.depth == 1
        assert outer.child_s == pytest.approx(inner.duration)
        assert outer.self_s == pytest.approx(outer.duration - inner.duration)
        by_plane = tr.by_plane()
        assert by_plane["runtime"] + by_plane["engine"] == pytest.approx(
            tr.total_s()
        )

    def test_per_pe_tracks_nest_independently(self):
        tr = TelemetrySession().tracer
        a = tr.begin("step", pe=0)
        b = tr.begin("step", pe=1)
        tr.end(b)
        tr.end(a)
        assert all(s.depth == 0 for s in tr.spans)

    def test_plane_defaults_to_first_dotted_segment(self):
        tr = TelemetrySession().tracer
        with tr.span("fetch.commit"):
            pass
        assert tr.spans[0].plane == "fetch"

    def test_misnested_exit_recovers(self):
        tr = TelemetrySession().tracer
        outer = tr.begin("outer")
        tr.begin("leaked")  # never ended (exception unwound past it)
        tr.end(outer)
        with tr.span("next"):
            pass
        assert tr.spans[-1].depth == 0

    def test_by_name_counts(self):
        tr = TelemetrySession().tracer
        for _ in range(3):
            with tr.span("step"):
                pass
        assert tr.by_name()["step"]["count"] == 3


# ---------------------------------------------------------------------- #
# module helpers: off = no-ops, activation is exclusive
# ---------------------------------------------------------------------- #
class TestHelpers:
    def test_off_helpers_are_noops(self):
        assert not tel.enabled()
        assert tel.current() is None
        sp = tel.span("anything")
        sp.nbytes = 123  # instrumented code writes attributes freely
        with sp:
            pass
        assert tel.begin("x") is None
        tel.end(None)
        tel.count("c", 5)

    def test_removed_helpers_and_knob_are_gone(self):
        # Spans always annotate while a session is active: no knob.
        assert not hasattr(tel, "gauge") and not hasattr(tel, "observe")
        assert "gauge" not in tel.__all__ and "observe" not in tel.__all__
        with pytest.raises(TypeError):
            TelemetrySession(annotate=True)

    def test_activate_twice_raises(self):
        with tel.active(TelemetrySession()):
            with pytest.raises(RuntimeError, match="already active"):
                tel.activate(TelemetrySession())
        assert not tel.enabled()

    def test_active_context_restores_on_error(self):
        with pytest.raises(KeyError):
            with tel.active(TelemetrySession()):
                raise KeyError("boom")
        assert not tel.enabled()

    def test_spanned_decorator(self):
        @tel.spanned("work.unit", plane="engine")
        def work():
            return 42

        assert work() == 42  # off: direct call
        with tel.active(TelemetrySession()) as session:
            assert work() == 42
        names = [s.name for s in session.tracer.spans]
        assert names == ["work.unit"]
        assert session.tracer.spans[0].plane == "engine"

    def test_count_routes_to_active_registry(self):
        with tel.active(TelemetrySession()) as session:
            tel.count("fetch.bytes", np.array([1.0, 2.0]))
            tel.count("fetch.bytes", np.array([3.0, 4.0]))
        np.testing.assert_array_equal(
            session.registry["fetch.bytes"].values, [4.0, 6.0]
        )


# ---------------------------------------------------------------------- #
# kernel profiling hooks
# ---------------------------------------------------------------------- #
class TestKernelProfiling:
    def test_profiled_dispatcher_records_calls(self):
        from repro.kernels import ops

        table = np.arange(12, dtype=np.float32).reshape(4, 3)
        idx = np.array([0, 2], dtype=np.int32)
        baseline = np.asarray(ops.gather_rows(table, idx))  # off: direct
        with tel.active(TelemetrySession()) as session:
            out = np.asarray(ops.gather_rows(table, idx))
        np.testing.assert_array_equal(out, baseline)
        assert session.registry["kernel.gather_rows.calls"].total == 1.0
        hist = session.registry["kernel.gather_rows.seconds"]
        assert hist.count == 1 and hist.sum > 0

    def test_profile_kernels_false_skips_hook(self):
        from repro.kernels import ops

        table = np.ones((4, 3), dtype=np.float32)
        idx = np.array([1], dtype=np.int32)
        with tel.active(TelemetrySession(profile_kernels=False)) as session:
            ops.gather_rows(table, idx)
        assert "kernel.gather_rows.calls" not in session.registry


# ---------------------------------------------------------------------- #
# the contract: off is bit-identical, on never perturbs
# ---------------------------------------------------------------------- #
class TestContract:
    @pytest.fixture(scope="class")
    def off_run(self, parts):
        t = DistributedTrainer(parts, **COMMON)
        return t, t.run()

    def test_telemetry_on_keeps_exact_digest(self, parts, off_run):
        t_off, r_off = off_run
        t_on = DistributedTrainer(parts, telemetry=True, **COMMON)
        r_on = t_on.run()
        assert (
            t_on.last_trace.exact_digest() == t_off.last_trace.exact_digest()
        )
        assert r_on.epoch_times == r_off.epoch_times
        assert r_off.telemetry is None
        assert r_on.telemetry is not None
        planes = r_on.telemetry["spans"]["by_plane"]
        for plane in ("runtime", "engine", "sampling", "decision"):
            assert plane in planes
        counters = r_on.telemetry["metrics"]["counters"]
        assert counters["fetch.bytes_modeled"]["total"] > 0

    def test_device_path_digest_and_device_counters(self, parts, off_run):
        t_off, _ = off_run
        t_dev = DistributedTrainer(
            parts, device="jnp", telemetry=True, **COMMON
        )
        r_dev = t_dev.run()
        assert (
            t_dev.last_trace.exact_digest() == t_off.last_trace.exact_digest()
        )
        counters = r_dev.telemetry["metrics"]["counters"]
        assert counters["device.h2d_bytes"]["total"] > 0
        assert counters["device.d2h_bytes"]["total"] > 0
        assert "device" in r_dev.telemetry["spans"]["by_plane"]
        assert any(k.startswith("kernel.") for k in counters)

    def test_legacy_runtime_emits_per_pe_tracks(self, parts, off_run):
        t_off, _ = off_run
        t_leg = DistributedTrainer(
            parts, runtime="legacy", telemetry=True, **COMMON
        )
        t_leg.run()
        assert (
            t_leg.last_trace.exact_digest() == t_off.last_trace.exact_digest()
        )
        pes = {s.pe for s in t_leg.last_telemetry.tracer.spans}
        assert pes == {-1, 0, 1, 2, 3}

    def test_session_passed_through_and_meta_stamped(self, parts):
        session = TelemetrySession(label="custom")
        t = DistributedTrainer(parts, telemetry=session, **COMMON)
        result = t.run()
        assert t.last_telemetry is session
        assert result.telemetry["label"] == "custom"
        assert session.meta["variant"] == "fixed"
        assert session.meta["num_pes"] == 4
        assert not tel.enabled()  # deactivated after the run

    def test_off_capture_holds_no_program_spans(self, parts, tmp_path):
        """A profiler capture of a telemetry-off training run holds none
        of the program's spans, and the run matches a telemetry-on one:
        same digest, same losses to the bit."""
        t_on = DistributedTrainer(parts, telemetry=True, **TRAIN)
        r_on = t_on.run()
        names = {s.name for s in t_on.last_telemetry.tracer.spans}
        assert set(TRAIN_PARTS) <= names
        t_off = DistributedTrainer(parts, **TRAIN)
        r_off, events = capture(tmp_path, t_off.run)
        assert events  # the capture itself worked
        assert not names & {e[1] for e in events}
        assert t_off.last_trace.exact_digest() == t_on.last_trace.exact_digest()
        assert r_off.losses == r_on.losses and r_off.losses

    def test_int64_fallback_counts_and_warns_once(self, parts, monkeypatch):
        from repro.kernels import ops

        t = DistributedTrainer(
            parts, device="jnp", telemetry=True, **COMMON
        )
        # ids past 2^31 now run device-resident in wide mode; only a
        # universe beyond WIDE_ID_MAX still takes the staged fallback.
        monkeypatch.setattr(
            type(t.graph), "num_nodes",
            property(lambda self: ops.WIDE_ID_MAX + 2),
        )
        with pytest.warns(RuntimeWarning, match="int32"):
            t.run()
        counters = t.last_telemetry.registry
        assert counters["device.fallback_int64"].total == 1.0
        # second run on the same trainer: counted again, not re-warned
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("error", RuntimeWarning)
            t.telemetry = TelemetrySession()
            t.run()
        assert t.last_telemetry.registry["device.fallback_int64"].total == 1.0


# ---------------------------------------------------------------------- #
# the training step from inside: train.* spans, upload bytes, profiler
# ---------------------------------------------------------------------- #
class TestTrainingStep:
    @pytest.fixture(scope="class", params=sorted(RUNTIMES))
    def traced(self, request, parts):
        t = DistributedTrainer(
            parts, telemetry=True, **RUNTIMES[request.param], **TRAIN
        )
        result = t.run()
        return t, result, t.last_telemetry.tracer.spans

    @staticmethod
    def _children(spans, parent):
        return [
            s for s in spans
            if s.pe == parent.pe and s.depth == parent.depth + 1
            and parent.t0 <= s.t0 and s.t1 <= parent.t1
        ]

    def test_parts_are_children_of_train(self, traced):
        t, result, spans = traced
        trains = [s for s in spans if s.name == "train"]
        steps, P = len(result.losses), t.parts.num_parts
        assert len(trains) == steps > 0
        for tr in trains:
            kids = [s.name for s in self._children(spans, tr)]
            assert kids == ["train.gather", "train.upload", "train.grads"] * P + [
                "train.update"
            ]
        for s in spans:
            if s.name in TRAIN_PARTS:
                assert s.pe == -1 and s.plane == "train"

    def test_self_times_sum_to_train(self, traced):
        _, _, spans = traced
        for tr in (s for s in spans if s.name == "train"):
            kids = self._children(spans, tr)
            total = tr.self_s + sum(k.self_s for k in kids)
            assert total == pytest.approx(tr.duration, abs=1e-9)

    def test_upload_bytes_are_the_uploaded_arrays(self, parts, monkeypatch):
        import jax

        t = DistributedTrainer(parts, telemetry=True, **TRAIN)
        gathered = []
        features_of = t._features_of

        def spy(mb):
            out = features_of(mb)
            gathered.append((out, mb.labels))
            return out

        monkeypatch.setattr(t, "_features_of", spy)
        result = t.run()
        P = t.parts.num_parts
        steps = len(result.losses)
        # every trainer step's, then the final accuracy's (not uploaded)
        assert len(gathered) == P * steps + 1
        gathered = gathered[:-1]
        label_bytes = jax.dtypes.canonicalize_dtype(np.int64).itemsize
        expect = sum(
            sum(x.nbytes for x in xs) + len(labels) * label_bytes
            for xs, labels in gathered
        )
        reg = t.last_telemetry.registry
        assert reg["device.h2d_bytes"].total == expect
        uploads = [s for s in t.last_telemetry.tracer.spans if s.name == "train.upload"]
        assert sum(s.nbytes for s in uploads) == expect
        gathers = [s for s in t.last_telemetry.tracer.spans if s.name == "train.gather"]
        assert sum(s.nbytes for s in gathers) == sum(
            sum(x.nbytes for x in xs) for xs, _ in gathered
        )
        # one float32 loss read back per trainer step
        assert reg["device.d2h_bytes"].total == 4 * P * steps

    @pytest.mark.parametrize("runtime", sorted(RUNTIMES))
    def test_profiler_capture_holds_every_span(self, parts, tmp_path, runtime):
        t = DistributedTrainer(parts, telemetry=True, **RUNTIMES[runtime], **TRAIN)
        _, events = capture(tmp_path, t.run)
        spans = t.last_telemetry.tracer.spans
        by_name: dict[str, list] = {}
        for line, name, a, b in events:
            by_name.setdefault(name, []).append((line, a, b))
        for name, row in t.last_telemetry.tracer.by_name().items():
            assert len(by_name.get(name, [])) == row["count"], name
        trains = by_name["train"]
        for name in TRAIN_PARTS:
            for line, a, b in by_name[name]:
                assert any(
                    ln == line and a0 <= a and b <= b0 for ln, a0, b0 in trains
                ), name
        if runtime == "device":
            # profiled kernel calls annotate as their counters are named
            kernels = {n for n in by_name if n.startswith("kernel.")}
            calls = {
                n[: -len(".calls")]
                for n in t.last_telemetry.registry.names()
                if n.startswith("kernel.") and n.endswith(".calls")
            }
            assert kernels == calls and calls
        assert len(spans) == sum(len(by_name[n]) for n in {s.name for s in spans})


# ---------------------------------------------------------------------- #
# exporters: JSONL round-trip + Chrome-trace validation (acceptance)
# ---------------------------------------------------------------------- #
class TestExport:
    @pytest.fixture(scope="class")
    def session(self, parts):
        t = DistributedTrainer(
            parts, runtime="legacy", telemetry=True, **COMMON
        )
        t.run()
        return t.last_telemetry

    def test_jsonl_round_trip(self, session, tmp_path):
        path = write_jsonl(session, tmp_path / "run.jsonl")
        artifact = load_jsonl(path)
        assert artifact["meta"]["label"] == "fixed"
        assert artifact["meta"]["provenance"]["schema"] == 1
        assert len(artifact["spans"]) == len(session.tracer.spans)
        rows = breakdown_rows(artifact)
        assert rows and {"plane", "spans", "self_s", "bytes"} <= set(rows[0])
        table = render_table(rows)
        assert "total" in table

    def test_load_jsonl_rejects_garbage(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_text("this is not json\n")
        with pytest.raises(ValueError, match="not a telemetry JSONL"):
            load_jsonl(bad)
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        with pytest.raises(ValueError, match="no telemetry rows"):
            load_jsonl(empty)

    def test_chrome_trace_validates(self, session, tmp_path):
        """Acceptance: the Chrome-trace JSON loads, spans nest within
        their parents, and per-PE thread tracks are present."""
        path = tmp_path / "trace.json"
        session.write_chrome_trace(path)
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        # per-PE tracks: host (tid 0) + one thread per trainer PE
        names = {
            e["args"]["name"]: e["tid"]
            for e in events
            if e.get("ph") == "M" and e["name"] == "thread_name"
        }
        assert names["host"] == 0
        for p in range(4):
            assert names[f"PE {p}"] == p + 1
        complete = [e for e in events if e.get("ph") == "X"]
        assert complete
        for e in complete:
            assert e["dur"] >= 0 and e["ts"] >= 0
        # spans nest: every depth>0 event lies inside a depth-1 parent
        # on the same track
        eps = 1e-3  # float µs rounding
        for e in complete:
            d = e["args"]["depth"]
            if d == 0:
                continue
            parents = [
                p for p in complete
                if p["tid"] == e["tid"] and p["args"]["depth"] == d - 1
                and p["ts"] - eps <= e["ts"]
                and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + eps
            ]
            assert parents, f"span {e['name']} has no enclosing parent"

    def test_chrome_trace_from_loaded_artifact(self, session, tmp_path):
        jsonl = write_jsonl(session, tmp_path / "run.jsonl")
        doc = chrome_trace(load_jsonl(jsonl))
        n_complete = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
        assert n_complete == len(session.tracer.spans)


# ---------------------------------------------------------------------- #
# CLI
# ---------------------------------------------------------------------- #
class TestCLI:
    @pytest.fixture(scope="class")
    def artifact(self, parts, tmp_path_factory):
        t = DistributedTrainer(parts, telemetry=True, **COMMON)
        t.run()
        path = tmp_path_factory.mktemp("tel") / "run.jsonl"
        write_jsonl(t.last_telemetry, path)
        return str(path)

    def test_summary(self, artifact, capsys):
        assert tel_main(["summary", artifact]) == 0
        out = capsys.readouterr().out
        assert "plane" in out and "total" in out and "# run:" in out

    def test_summary_json(self, artifact, tmp_path, capsys):
        out_json = str(tmp_path / "rows.json")
        assert tel_main(["summary", artifact, "--json", out_json]) == 0
        rows = json.load(open(out_json))["rows"]
        assert any(r["plane"] == "engine" for r in rows)
        capsys.readouterr()

    def test_chrome(self, artifact, tmp_path, capsys):
        out = str(tmp_path / "trace.json")
        assert tel_main(["chrome", artifact, "--out", out]) == 0
        doc = json.loads(open(out).read())
        assert doc["traceEvents"]
        capsys.readouterr()

    def test_missing_artifact_exits_2(self, capsys):
        assert tel_main(["summary", "/nonexistent/run.jsonl"]) == 2
        assert "error:" in capsys.readouterr().err

    def test_garbage_artifact_exits_2(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        bad.write_text("nope\n")
        assert tel_main(["summary", str(bad)]) == 2
        assert "error:" in capsys.readouterr().err

    def test_unknown_subcommand_exits_2(self):
        with pytest.raises(SystemExit) as exc:
            tel_main(["frobnicate"])
        assert exc.value.code == 2


# ---------------------------------------------------------------------- #
# calibration
# ---------------------------------------------------------------------- #
class TestCalibration:
    def test_recovers_known_constants(self):
        rng = np.random.default_rng(0)
        alpha, bw = 5e-4, 1e6
        nbytes = rng.integers(1_000, 500_000, size=64)
        seconds = alpha + nbytes / bw
        cal = fit_alpha_bw(nbytes, seconds)
        assert cal.alpha == pytest.approx(alpha, rel=1e-6)
        assert cal.link_bw == pytest.approx(bw, rel=1e-6)
        assert cal.max_abs_err_s < 1e-9
        np.testing.assert_allclose(cal.predict(nbytes), seconds)

    def test_zero_byte_samples_dropped(self):
        nbytes = [0, 0, 100, 200]
        seconds = [9.0, 9.0, 1e-3, 2e-3]
        cal = fit_alpha_bw(nbytes, seconds)
        assert cal.n_samples == 2

    def test_needs_two_distinct_byte_counts(self):
        with pytest.raises(ValueError, match="distinct"):
            fit_alpha_bw([100, 100], [1.0, 1.0])

    def test_noise_degenerates_gracefully(self):
        # Negative trend: slope <= 0 => infinite bandwidth, mean alpha
        from repro.telemetry import calibrate as _cal_mod

        _cal_mod._warned_degenerate_fit = False
        with pytest.warns(RuntimeWarning, match="non-positive slope"):
            cal = fit_alpha_bw([100, 200, 300], [3e-3, 2e-3, 1e-3])
        assert cal.link_bw == float("inf")
        assert cal.alpha == pytest.approx(2e-3)

    def test_degenerate_fit_warns_once(self):
        import warnings as _warnings

        from repro.telemetry import calibrate as _cal_mod

        _cal_mod._warned_degenerate_fit = False
        with pytest.warns(RuntimeWarning, match="non-positive slope"):
            fit_alpha_bw([100, 200, 300], [3e-3, 2e-3, 1e-3])
        # second degenerate fit: same clamp, no repeat warning
        with _warnings.catch_warnings():
            _warnings.simplefilter("error", RuntimeWarning)
            cal = fit_alpha_bw([100, 200, 300], [5e-3, 4e-3, 3e-3])
        assert cal.link_bw == float("inf")
        assert cal.alpha == pytest.approx(4e-3)

    def test_healthy_fit_does_not_warn(self):
        import warnings as _warnings

        from repro.telemetry import calibrate as _cal_mod

        _cal_mod._warned_degenerate_fit = False
        with _warnings.catch_warnings():
            _warnings.simplefilter("error", RuntimeWarning)
            cal = fit_alpha_bw([100, 200, 300], [1e-3, 2e-3, 3e-3])
        assert np.isfinite(cal.link_bw)

    def test_to_time_model(self):
        cal = Calibration(
            alpha=1e-3, link_bw=2e6, n_samples=10, max_abs_err_s=0.0
        )
        tm = cal.to_time_model(t_ddp=0.1)
        assert tm.alpha == 1e-3 and tm.link_bw == 2e6 and tm.t_ddp == 0.1

    def test_calibrate_from_store_trace(self, parts):
        t = DistributedTrainer(parts, feature_store=True, **COMMON)
        t.run()
        cal = calibrate_from_trace(t.last_trace)
        assert cal.n_samples >= 2
        assert cal.alpha >= 0.0
        assert np.isfinite(cal.alpha)

    def test_calibrate_from_trace_needs_store_streams(self, parts):
        t = DistributedTrainer(parts, **COMMON)
        t.run()
        with pytest.raises(ValueError, match="measured store streams"):
            calibrate_from_trace(t.last_trace)

    def test_calibrate_from_session(self, parts):
        t = DistributedTrainer(
            parts, feature_store=True, telemetry=True, **COMMON
        )
        t.run()
        cal = calibrate_from_session(t.last_telemetry)
        assert cal.n_samples >= 2

    def test_calibrate_from_empty_session_raises(self):
        with pytest.raises(ValueError, match="store.gather"):
            calibrate_from_session(TelemetrySession())


# ---------------------------------------------------------------------- #
# sweep + provenance integration
# ---------------------------------------------------------------------- #
class TestIntegration:
    def test_provenance_header(self):
        p = provenance()
        assert p["schema"] == 1
        for key in ("git_sha", "platform", "python", "jax", "numpy"):
            assert isinstance(p[key], str) and p[key]
        json.dumps(p)

    def test_sweep_rows_carry_telemetry_brief(self):
        from repro.runtime.sweep import (
            SweepConfig,
            run_sweep,
            sweep_artifact,
        )

        cfg = SweepConfig(
            num_parts=2, batch_size=8, fanouts=(3, 5), epochs=1
        )
        rows = run_sweep([cfg], scale=0.05, telemetry=True)
        assert len(rows) == 1
        brief = rows[0]["telemetry"]
        assert brief["span_count"] > 0
        assert "engine" in brief["by_plane"]
        assert not tel.enabled()
        payload = sweep_artifact(rows)
        assert payload["provenance"]["schema"] == 1

    def test_agent_lane_spans_and_pipe_counters(self):
        from repro.core import LLMAgent, make_backend

        g = generate("products", seed=0, scale=0.05)
        parts = partition_graph(g, 2)
        deciders = [LLMAgent(make_backend("gemma3-4b"), None) for _ in range(2)]
        t = DistributedTrainer(
            parts, variant="rudder", deciders=deciders, telemetry=True,
            epochs=1, batch_size=8, fanouts=(3, 5), train_model=False,
            buffer_frac=0.25, interval=4,
        )
        t.run()
        summary = t.last_telemetry.summary()
        counters = summary["metrics"]["counters"]
        assert counters["agent.requests"]["total"] > 0
        assert "agent" in summary["spans"]["by_plane"]
        # the decision pipe saw traffic: per-PE submit/ready counters
        assert counters["pipe.submitted"]["total"] > 0
        assert counters["pipe.ready"]["total"] > 0
        assert len(counters["pipe.submitted"]["values"]) == 2
