"""One run of one benchmark cell, driven by the files ``BENCHMARK.json`` names.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix. The configuration is ``<bench>/configs/<config>.json``: the graph's
published numbers and its twin's shape (``graphs.py`` makes it), the
number of trainers, the model (``model``) and its job parameters, and
the limits of the correctness check. The model is a module
``<bench>/models/<model>.py``: its plain reference, its weights' layout
and its work counts (``load_model``). The mix is
``<bench>/traffic/<traffic>.json``: the rest of the job's parameters
(variant, controller, batch, buffer) and whether the job keeps a
prefetch buffer (``buffer``). Every metric is a reader
``<bench>/metrics/<name>.py`` with a function ``read(run) -> float |
None`` over the :class:`Run` record. Nothing here knows a cell, a
configuration, a model, a mix or a metric by name.

A run:

1. makes the graph and its partitioning, or loads them from
   ``<bench>/.cache/graphs`` (``graphs.partitioned``);
2. builds one ``DistributedTrainer`` from the configuration's and the
   mix's job parameters with ``seed``, gives it the model's weights,
   made from ``seed``, and records what its sampler hands each step;
3. warms up with one ``run()`` of whole epochs, three steps at least:
   this compiles (or loads from the compile cache) every program the
   window uses and fills the buffer;
4. times one further ``run()`` whose epoch count is set from the warm
   steps' time so that it lasts about ``seconds``;
5. checks the result (``check.py``): the reference follows every step
   of the warm-up and the window's first three, and the prefetch
   accounting of every step is replayed; then it reads the metrics.
"""

from __future__ import annotations

import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import check, devtrace, graphs, reference

SPEC_FILE = "BENCHMARK.json"
#: Steps at least in the warm-up; its first are compared step by step.
WARM_STEPS = 3
#: The window's first steps that the reference follows too.
WINDOW_STEPS = 3
#: Host annotation around the measured window in a profiler trace.
ANCHOR = "bench.window"
#: JAX's monitoring event for each program compiled or loaded from the
#: persistent cache; none may fire inside the window.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# --------------------------------------------------------------------- #
# What BENCHMARK.json names
# --------------------------------------------------------------------- #
@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    bench_dir: Path
    #: The configuration's model module (``load_model``).
    model: object


def load_spec(root: Path) -> dict:
    with open(root / SPEC_FILE) as f:
        return json.load(f)


def resolve_cell(root: Path, spec: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {SPEC_FILE}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = root / spec["paths"][0]
    with open(root / cfg["file"]) as f:
        config = json.load(f)
    if "model" not in config:
        raise ValueError(
            f"{root / cfg['file']} names no model: give it \"model\", a module "
            f"<model>.py under {bench_dir / 'models'}"
        )
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    model = load_model(bench_dir, config["model"])
    return Cell(name, config, mix, int(w["chips"]), bench_dir, model)


def metric_specs(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer ones (those listing the cell, or without a
    list, those that move one of its end-to-end metrics)."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m
        for m in spec["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]


def _load_module(bench_dir: Path, kind: str, name: str):
    """The module ``<bench>/<kind>/<name>.py``, loaded by path."""
    path = bench_dir / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    # Registered before it runs: a dataclass looks its module up.
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: Path, name: str):
    return _load_module(bench_dir, "metrics", name).read


def load_model(bench_dir: Path, name: str):
    """The model module ``<bench>/models/<name>.py``: ``shapes(config,
    trainer_kwargs)``, ``LEAVES``, ``init_weights(seed, shapes)``,
    ``train(w0, table, steps, lr, dtype, *, fault)``, ``grads_flops``,
    ``grads_bytes`` and ``PROGRAM`` (``bench/README.md``)."""
    return _load_module(bench_dir, "models", name)


# --------------------------------------------------------------------- #
# What the timed path is fed
# --------------------------------------------------------------------- #
class SamplerSpy:
    """Stands in for the trainer's ``sampler_plane`` and records, per
    ``run()`` call, every step's minibatches (one per trainer), the
    weights at the start of each step and when the step began; the
    sampling itself is the plane's. The weights after a call's last step
    are added by :meth:`end`."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.plane = trainer.sampler_plane
        self.runs: list[dict] = []

    def __getattr__(self, name):
        return getattr(self.plane, name)

    def _record(self, minibatches) -> None:
        run = self.runs[-1]
        run["weights"].append(self.trainer.params)
        run["t"].append(time.perf_counter())
        run["batches"].append(
            [(mb.seeds, list(mb.layer_nbrs), mb.labels) for mb in minibatches]
        )

    def sample_all(self, seed_blocks, rng, **kw):
        minibatches, remote = self.plane.sample_all(seed_blocks, rng, **kw)
        self._record(minibatches)
        return minibatches, remote

    def sample_all_raw(self, seed_blocks, rng, **kw):
        minibatches, touched = self.plane.sample_all_raw(seed_blocks, rng, **kw)
        self._record(minibatches)
        return minibatches, touched

    def begin(self) -> None:
        self.runs.append({"batches": [], "weights": [], "t": []})

    def end(self) -> None:
        self.runs[-1]["weights"].append(self.trainer.params)

    def steps(self, k: int, n: int | None = None) -> list[list[tuple]]:
        """The minibatches of run ``k``'s first ``n`` steps (all without
        ``n``), ``[step][trainer]``, each as ``(seeds, hops, labels)``
        with every hop of the sampler's ``layer_nbrs``."""
        return self.runs[k]["batches"][:n]

    def weights(self, k: int) -> list:
        """Run ``k``'s weights before each step and after its last."""
        return self.runs[k]["weights"]

    def step_seconds(self, k: int) -> float:
        """Median time of run ``k``'s steps after its first, from the
        start of one step's sampling to the next's."""
        t = self.runs[k]["t"]
        return float(statistics.median(np.diff(t[1:]))) if len(t) > 2 else t[-1] - t[0]


def _leaves(tree, names: tuple) -> dict:
    import jax

    return {
        k: np.asarray(v, np.float64) for k, v in zip(names, jax.tree_util.tree_leaves(tree))
    }


def _set_weights(trainer, w0: dict, names: tuple) -> None:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(trainer.params)
    new = [w0[k] for k in names]
    if len(leaves) != len(new) or any(a.shape != b.shape for a, b in zip(leaves, new)):
        raise ValueError(
            f"the trainer's weights do not have the layout {names}: "
            f"{[a.shape for a in leaves]}"
        )
    trainer.params = jax.tree_util.tree_unflatten(treedef, new)


# --------------------------------------------------------------------- #
# The record each metric reader reads
# --------------------------------------------------------------------- #
@dataclass
class Run:
    cell: Cell
    device_kind: str
    #: The model's ``Shapes`` for this cell.
    shapes: object
    trainers: int
    setup_s: float
    window_s: float
    steps: int
    seeds: int
    #: The program's ``RunResult`` of the window's ``run()``.
    result: object
    #: The trainer after the window.
    trainer: object = None
    #: The window's telemetry session (traced runs only).
    session: object | None = None
    #: The window's profiler trace, reduced by ``devtrace`` (traced runs).
    trace: dict | None = None

    @property
    def model(self):
        """The configuration's model module."""
        return self.cell.model

    @property
    def logs(self) -> list:
        """The program's per-trainer logs of the window's steps."""
        return self.result.logs

    def span_self_s(self, names=(), prefixes=()) -> float | None:
        """Summed self time of the window's spans with one of ``names``
        or a name starting with one of ``prefixes``; None untraced or
        when no such span ran."""
        if self.session is None:
            return None
        sel = [
            s.self_s
            for s in self.session.tracer.spans
            if s.name in names or s.name.startswith(tuple(prefixes))
        ]
        return sum(sel) if sel else None

    def span_total_s(self, names=()) -> float | None:
        """Summed inclusive time of the window's spans with one of
        ``names``; None untraced or when no such span ran."""
        if self.session is None:
            return None
        sel = [s.duration for s in self.session.tracer.spans if s.name in names]
        return sum(sel) if sel else None


# --------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------- #
def _trainer_kwargs(cell: Cell) -> dict:
    kw = dict(cell.config["trainer"])
    kw.update(cell.mix["trainer"])
    if "fanouts" in kw:
        kw["fanouts"] = tuple(kw["fanouts"])
    return kw


def _check_config(cell: Cell, graph) -> None:
    c = cell.config
    got = {
        "num_nodes": graph.num_nodes,
        "num_edges": graph.num_edges,
        "feature_dim": graph.features.shape[1],
        "num_classes": graph.num_classes,
        "train_nodes": len(graph.train_nodes),
    }
    bad = {k: (c[k], v) for k, v in got.items() if c[k] != v}
    if bad:
        raise ValueError(f"graph differs from {cell.config['name']}: {bad}")


@dataclass
class Job:
    """One trainer built for a cell, with what the check needs of it."""

    parts: object
    halos: np.ndarray
    trainer: object
    spy: SamplerSpy
    w0: dict
    model: object
    shapes: object
    kwargs: dict
    trainers: int
    uses_buffer: bool
    runs: list = field(default_factory=list)

    @property
    def lr(self) -> float:
        return float(self.kwargs.get("lr", 1e-2))

    def run(self):
        """One ``run()`` of the trainer, recorded by the spy."""
        self.spy.begin()
        res = self.trainer.run()
        self.spy.end()
        self.runs.append(res)
        return res


def build(cell: Cell, seed: int, cache_dir: Path | None = None) -> Job:
    """The cell's trainer with the model's weights and the spy."""
    from repro.gnn import DistributedTrainer

    parts, halos = graphs.partitioned(
        cell.config, cache_dir or cell.bench_dir / ".cache" / "graphs"
    )
    graph = parts.graph
    _check_config(cell, graph)
    P = int(cell.config["trainers"])
    kw = _trainer_kwargs(cell)
    tr = DistributedTrainer(parts, seed=seed, epochs=1, **kw)
    if tr.parts.num_parts != P:
        raise ValueError(f"partitioned {tr.parts.num_parts}-way, configured {P}")
    model = cell.model
    shapes = model.shapes(cell.config, kw)
    w0 = model.init_weights(seed, shapes)
    _set_weights(tr, w0, model.LEAVES)
    spy = SamplerSpy(tr)
    tr.sampler_plane = spy
    return Job(parts, halos, tr, spy, w0, model, shapes, kw, P, bool(cell.mix["buffer"]))


def warm_up(job: Job):
    """The first steps, through the window's own call: whole epochs,
    enough for the reference's ``WARM_STEPS``."""
    job.trainer.epochs = math.ceil(WARM_STEPS / job.trainer.mb_per_epoch)
    return job.run()


def followed_steps(job: Job) -> tuple[list, int]:
    """The steps the reference follows, ``[step][trainer]``: every step of
    the warm-up, then the window's first ``WINDOW_STEPS``; and the index
    of the window's first step among them."""
    warm = job.spy.steps(0)
    return warm + job.spy.steps(1, WINDOW_STEPS), len(warm)


def program_path(job: Job) -> tuple[list[float], list[dict]]:
    """The program's losses and weights after each followed step."""
    warm, window = job.spy.weights(0), job.spy.weights(1)
    n = min(WINDOW_STEPS, len(job.runs[1].losses))
    losses = list(job.runs[0].losses) + list(job.runs[1].losses[:n])
    after = [_leaves(w, job.model.LEAVES) for w in warm[1:] + window[1 : n + 1]]
    return losses, after


def reference_run(job: Job, table=None, **kw):
    """The model's ``train`` over the followed steps, with the feature
    table on the device (``table``, or uploaded here)."""
    steps, _ = followed_steps(job)
    if table is None:
        table = reference.device_table(job.parts.graph.features)
    return job.model.train(job.w0, table, steps, job.lr, **kw)


def steps_unchanged(job: Job) -> int:
    """Steps of every recorded run after which the weights are the
    same as before it, bit for bit."""
    import jax
    import jax.numpy as jnp

    same = jax.jit(lambda a, b: jnp.array(
        [jnp.array_equal(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                              jax.tree_util.tree_leaves(b))]
    ).all())
    n = 0
    for k in range(len(job.runs)):
        w = job.spy.weights(k)
        n += sum(bool(same(a, b)) for a, b in zip(w[:-1], w[1:]))
    return n


def accounting_mismatches(job: Job) -> int:
    """Replays the prefetch accounting of every recorded run."""
    frac = job.kwargs.get("buffer_frac", 0.25)
    acc = reference.Accounting(
        [max(int(h * frac), 1) for h in job.halos],
        job.parts.part_of,
        uses_buffer=job.uses_buffer,
    )
    mismatches = 0
    for k, res in enumerate(job.runs):
        decisions = np.array([lg.decisions for lg in res.logs]).T
        expect = acc.run(job.spy.steps(k, len(res.losses)), decisions)
        mismatches += check.accounting_mismatches(res.logs, expect)
    return mismatches


def readings(job: Job) -> dict:
    """Every number the check compares, over the warm-up and the window."""
    _, window_at = followed_steps(job)
    losses, after = program_path(job)
    out = check.training_readings(
        job.w0, losses, after, *reference_run(job), job.lr, window_at
    )
    out["steps_unchanged"] = steps_unchanged(job)
    out["accounting_mismatches"] = accounting_mismatches(job)
    return out


def _breakdown(run: Run, anchor_perf_s: float) -> dict:
    tr = run.trace
    offset = tr["window"][0] - anchor_perf_s * 1e9
    origin = run.session.tracer.origin
    spans = [
        (s.name, (origin + s.t0) * 1e9 + offset, (origin + s.t1) * 1e9 + offset)
        for s in run.session.tracer.spans
        if s.pe == -1
    ]
    return {
        "device_ops": devtrace.top_ops(tr),
        "idle_gaps": devtrace.idle_by_host(tr, devtrace.host_segments(spans)),
    }


def run_cell(
    root: Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    cache_dir: Path | None = None,
) -> dict:
    """Run one cell on JAX's default device; returns the result line."""
    import jax

    spec = load_spec(root)
    cell = resolve_cell(root, spec, workload)
    wanted = metric_specs(spec, workload, trace)
    readers = {m["name"]: load_reader(cell.bench_dir, m["name"]) for m in wanted}
    devices = jax.devices()
    dev = devices[0]

    job = build(cell, seed, cache_dir)
    t_built = time.perf_counter()
    tr = job.trainer
    warm_up(job)
    epoch_s = job.spy.step_seconds(0) * tr.mb_per_epoch
    tr.epochs = max(1, round(seconds / epoch_s))
    print(
        f"bench: built in {t_built - t_start:.3f} s, warm-up of "
        f"{len(job.runs[0].losses)} steps in {time.perf_counter() - t_built:.3f} s; "
        f"window of {tr.epochs} epochs of {tr.mb_per_epoch} steps",
        file=sys.stderr,
    )

    session = logdir = None
    if trace:
        from repro.telemetry import TelemetrySession

        session = TelemetrySession(label=workload)
        tr.telemetry = session
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)

    compiles = []

    def on_event(event, seconds, **kw):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        if trace:
            with jax.profiler.TraceAnnotation(ANCHOR):
                res = job.run()
                jax.block_until_ready(tr.params)
        else:
            res = job.run()
            jax.block_until_ready(tr.params)
    finally:
        t1 = time.perf_counter()
        jax.monitoring.unregister_event_duration_listener(on_event)
    if compiles:
        print(f"bench: compiled in the window: {compiles}", file=sys.stderr)

    reduced = None
    if trace:
        jax.profiler.stop_trace()
        try:
            reduced = devtrace.read_xplane(logdir, ANCHOR)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    steps = len(res.losses)
    run = Run(
        cell=cell,
        device_kind=dev.device_kind,
        shapes=job.shapes,
        trainers=job.trainers,
        setup_s=t0 - t_start,
        window_s=t1 - t0,
        steps=steps,
        seeds=steps * job.trainers * tr.batch_size,
        result=res,
        trainer=tr,
        session=session,
        trace=reduced,
    )

    # Correctness, once the window has closed and its peak been read.
    t2 = time.perf_counter()
    correct, checks = check.verdict(readings(job), cell.config["limits"])
    print(f"bench: the check took {time.perf_counter() - t2:.3f} s", file=sys.stderr)
    failed = sum(not math.isfinite(x) for x in res.losses)

    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out = {
        "correct": bool(correct and failed == 0),
        "attempted": steps,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = devtrace.busy_s(reduced)
        device["window_s"] = devtrace.window_s(reduced)
        out["breakdown"] = _breakdown(run, t0)
    out["checks"] = checks
    return out
