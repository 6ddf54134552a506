"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus per-row [figNN]
detail lines). Usage::

    PYTHONPATH=src python -m benchmarks.run            # full suite
    PYTHONPATH=src python -m benchmarks.run fig03 tab04
    PYTHONPATH=src python -m benchmarks.run --sweep    # scenario grid

``--sweep`` runs the stock configuration grid
(num_parts x batch_size x fanout x controller) through the vectorized
``repro.runtime`` engine in this single process and prints one CSV row
per cell; extra positional args filter cells by substring of their
label (e.g. ``--sweep p4 massivegnn``). Sweep options:

* ``--policies=rudder,recency,...`` — widen the grid along the
  scoring/eviction policy axis (see ``repro.core.scoring.POLICIES``;
  ``--policies=all`` selects the whole zoo);
* ``--graphs=products,rmat,powerlaw,...`` — the graph-scenario axis
  (dataset presets of ``repro.graph.generate.DATASET_PRESETS``,
  including the RMAT / power-law families; ``--graphs=all`` sweeps
  every preset);
* ``--topology=none,rack,torus,...`` — the cluster cost-model axis
  (``repro.graph.generate.TOPOLOGIES``; ``none`` is the flat §4.5.3
  model, ``--topology=all`` adds every named topology);
* ``--time-engine=closed_form,event`` — the wall-clock model axis
  (``repro.sim``; ``event`` is the discrete-event cluster simulator,
  bit-identical to ``closed_form`` until a scenario is injected);
* ``--stragglers=none,one-slow,...`` / ``--congestion=none,hot-home,...``
  — scenario presets for the event engine (per-PE compute multipliers
  and seeded jitter; max–min fair home-egress sharing and transient
  degradation). Scenario cells are generated for event-engine cells
  only — the closed form cannot express them;
* ``--feature-store`` — serve every cell's miss/placement streams from
  the sharded ``repro.store.FeatureStore`` data plane (real gathers;
  rows gain measured ``bytes_measured``/``bytes_modeled``/
  ``fetch_seconds_measured`` columns while the decision/byte streams
  stay bit-identical to the modeled path);
* ``--telemetry`` — run every cell under its own
  ``repro.telemetry.TelemetrySession``; rows gain a ``telemetry`` field
  (wall seconds, span count, per-plane seconds, counter totals) in the
  JSON artifact while all exact metrics stay bit-identical;
* ``--quick`` — shrink the grid (1 partition count x 1 batch x 1
  fanout, 2 epochs) for the CI smoke legs;
* ``--json=PATH`` — additionally write the deterministic sweep artifact
  (sorted cells, sorted keys) consumed by the CI ``bench-smoke`` job;
* ``--gate`` — exit non-zero if any cell is NaN/empty/non-finite (the
  perf-trajectory gate applied before the artifact is uploaded);
* ``--trace=DIR`` — record every cell's full run trace
  (``repro.trace``: seeds, frontiers, miss sets, decisions, step times)
  with a replayable manifest under ``DIR``; each row's ``trace`` field
  names its artifact (``<label>-<mode>-s<seed>-<cellhash>.npz`` — the
  hash suffix keeps cells distinct on axes the label omits). Any cell
  can then be re-run or compared in isolation with
  ``python -m repro.trace replay/diff``.
"""

import os
import sys
import time
import traceback

MODULES = [
    "fig01_unique_remotes",
    "fig03_hits_strategies",
    "fig12_baseline_perf",
    "fig13_improvement",
    "fig14_comm_volume",
    "fig15_massivegnn",
    "fig16_tradeoff",
    "tab02_sync_async",
    "tab04_pass1",
    "fig18_unseen",
    "fig20_trajectory",
    "tab05_moe_agents",
    "kernels_micro",
    "roofline_table",
]


def _parse_axis(arg: str, options, all_value: tuple) -> tuple | None:
    """Parse ``--axis=a,b,c`` against valid options ('all' = every one)."""
    name, spec = arg.split("=", 1)
    values = all_value if spec == "all" else tuple(v for v in spec.split(",") if v)
    unknown = [v for v in values if v not in options]
    if unknown or not values:
        print(
            f"unknown {name} {unknown or spec!r}; "
            f"options: {sorted(options)} or 'all'",
            file=sys.stderr,
        )
        return None
    return values


def run_sweep_cli(selected: list[str]) -> int:
    from repro.core.scoring import POLICIES
    from repro.graph import (
        CONGESTION_PRESETS,
        DATASET_PRESETS,
        STRAGGLER_PRESETS,
        TOPOLOGIES,
    )
    from repro.runtime import (
        default_grid,
        run_sweep,
        validate_rows,
        write_sweep_json,
    )
    from repro.sim import TIME_ENGINES

    policies = ("rudder",)
    datasets = ("products",)
    topologies = ("none",)
    time_engines = ("closed_form",)
    stragglers = ("none",)
    congestions = ("none",)
    json_path = None
    gate = False
    quick = False
    feature_store = False
    trace_dir = None
    telemetry = False
    terms = []
    for arg in selected:
        if arg.startswith("--policies="):
            policies = _parse_axis(arg, POLICIES, tuple(sorted(POLICIES)))
            if policies is None:
                return 2
        elif arg.startswith("--graphs="):
            datasets = _parse_axis(
                arg, DATASET_PRESETS, tuple(sorted(DATASET_PRESETS))
            )
            if datasets is None:
                return 2
        elif arg.startswith("--topology="):
            options = ("none",) + tuple(TOPOLOGIES)
            topologies = _parse_axis(arg, options, options)
            if topologies is None:
                return 2
        elif arg.startswith("--time-engine="):
            time_engines = _parse_axis(arg, TIME_ENGINES, tuple(TIME_ENGINES))
            if time_engines is None:
                return 2
        elif arg.startswith("--stragglers="):
            options = ("none",) + tuple(STRAGGLER_PRESETS)
            stragglers = _parse_axis(arg, options, options)
            if stragglers is None:
                return 2
        elif arg.startswith("--congestion="):
            options = ("none",) + tuple(CONGESTION_PRESETS)
            congestions = _parse_axis(arg, options, options)
            if congestions is None:
                return 2
        elif arg == "--quick":
            quick = True
        elif arg == "--feature-store":
            feature_store = True
        elif arg == "--telemetry":
            telemetry = True
        elif arg.startswith("--json="):
            json_path = arg.split("=", 1)[1]
        elif arg.startswith("--trace="):
            trace_dir = arg.split("=", 1)[1]
        elif arg == "--gate":
            gate = True
        else:
            terms.append(arg)
    wants_scenarios = stragglers != ("none",) or congestions != ("none",)
    if wants_scenarios and "event" not in time_engines:
        print(
            "--stragglers/--congestion need --time-engine=event (or =all)",
            file=sys.stderr,
        )
        return 2
    shrink = (
        dict(
            num_parts=(4,),
            batch_sizes=(16,),
            fanouts=((5, 10),),
            epochs=2,
        )
        if quick
        else {}
    )
    grid = default_grid(
        datasets=datasets,
        policies=policies,
        topologies=topologies,
        time_engines=time_engines,
        stragglers=stragglers,
        congestions=congestions,
        feature_store=feature_store,
        **shrink,
    )
    if terms:
        # AND semantics: every term must match, so extra terms narrow.
        grid = [c for c in grid if all(s in c.label() for s in terms)]
    if not grid:
        print(f"no sweep cells match {terms!r}", file=sys.stderr)
        return 1
    t0 = time.time()
    rows = run_sweep(grid, verbose=True, trace_dir=trace_dir, telemetry=telemetry)
    print(
        "label,dataset,variant,policy,topology,time_engine,stragglers,"
        "congestion,num_parts,batch_size,fanouts,"
        "steady_pct_hits,comm_per_minibatch,mean_epoch_time"
    )
    for r in rows:
        fan = "x".join(str(f) for f in r["fanouts"])
        print(
            f"{r['label']},{r['dataset']},{r['variant']},{r['policy']},"
            f"{r['topology']},{r['time_engine']},{r['stragglers']},"
            f"{r['congestion']},{r['num_parts']},{r['batch_size']},{fan},"
            f"{r['steady_pct_hits']},{r['comm_per_minibatch']},"
            f"{r['mean_epoch_time']}"
        )
    print(
        f"# sweep: {len(rows)} configurations in {time.time()-t0:.1f}s "
        f"(one process)",
        file=sys.stderr,
    )
    if json_path:
        write_sweep_json(rows, json_path)
        print(f"# sweep artifact written to {json_path}", file=sys.stderr)
    if gate:
        problems = validate_rows(rows)
        if problems:
            for problem in problems:
                print(f"# GATE FAIL: {problem}", file=sys.stderr)
            return 1
        print(f"# gate: {len(rows)} cells sound", file=sys.stderr)
    return 0


def _use_checkout_compile_cache() -> None:
    """Keep JAX's persistent compile cache at one fixed, gitignored path
    of the checkout, so later runs from it reuse compiled programs. An
    explicit ``JAX_COMPILATION_CACHE_DIR`` is left to JAX."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))


def main() -> int:
    _use_checkout_compile_cache()
    selected = sys.argv[1:]
    if "--sweep" in selected:
        selected.remove("--sweep")
        return run_sweep_cli(selected)
    failures = 0
    print("name,us_per_call,derived")
    for name in MODULES:
        if selected and not any(s in name for s in selected):
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception:  # noqa: BLE001 — keep the suite running
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
            failures += 1
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
