"""One benchmark run: build a cell from its files, drive the program's
FedSDD rounds, time a window, check the first rounds against the
reference, and reduce the trace.

Everything that belongs to one configuration, traffic mix, model family
or per-layer metric sits in a file of its own, found by name:

- ``configs/<config>.json``: the model and data set, with its source, the
  keys it changes from the source (``reduced``), what it assumes, and its
  ``family``;
- ``families/<family>.py``: what the configuration's kind of model
  brings, as module-level names:

  - ``federation(cfg, mix, data_seed)``: ``(client_data, server_inputs)``,
    each client's shard an ``(inputs, targets)`` pair of numpy arrays
    whose leading axis is the example axis;
  - ``program(cfg, server_inputs, server_batch)``: the program's task
    functions, built from ``src/``: ``loss_fn``, ``logits_fn`` (rows x
    V), ``make_batch``, ``server_batches``, and ``features_fn`` /
    ``head_fn`` where the model has that split (else None);
  - ``make_init(cfg, weight_seed)``: ``init(key) -> params``, the
    benchmark's own initial weights;
  - ``plain_model(cfg)``: the benchmark's own model in ``jax.numpy``,
    importing nothing of the program: ``(logits(params, inputs) ->
    (rows, V), loss(params, inputs, targets, rows))``, the second the
    local training loss over the minibatch ``rows`` of the client data;
  - ``round_flops(cfg, job, runs, teachers)``: the model FLOPs of a round;
  - ``REFERENCE_BLOCK``: ``(clients, teachers)`` that the round reference
    runs at once, None for all;

  and, for the benchmark's tests (``tests/conftest.py``), ``shrink(cell)``:
  the cell cut to a size the CPU runs in seconds;
- ``traffic/<mix>.json``: the federation (population, job, warm-up) and
  the program options the mix pins;
- ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None`` for
  each per-layer metric;
- ``limits/<workload>.json``: the limits of the cell's compared numbers.

The program is imported from ``src/`` beside this directory: the
system under test, its FedSDD runner, models and kernels.  Data,
initial weights, FLOP counts, peaks, the trace reduction and the
reference are the benchmark's own.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FAMILIES = os.path.join(BENCH, "families")
# what the harness and the round reference take from a family
FAMILY_NAMES = ("federation", "program", "make_init", "plain_model",
                "round_flops", "REFERENCE_BLOCK")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# a traced run's window: the whole rounds of its first seconds, since a
# trace of a longer one takes minutes to write and read
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ the files
def _json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The workload's entry of ``BENCHMARK.json`` with its configuration
    and traffic mix and the per-layer metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    config = _json("configs", f"{cell['config']}.json")
    family_of(config)
    from check import load_limits
    return {"name": workload, "chips": cell["chips"], "config": config,
            "mix": _json("traffic", f"{cell['traffic']}.json"),
            "end_to_end": end_to_end, "per_layer": per_layer,
            "limits": load_limits(BENCH, workload)}


def family_of(cfg: dict):
    """The configuration's model family: ``families/<family>.py``, loaded
    by path once per process."""
    name = cfg.get("family")
    path = os.path.join(FAMILIES, f"{name}.py")
    if not name or not os.path.isfile(path):
        raise SystemExit(f"configuration {cfg.get('name')!r} names the "
                         f"family {name!r}, which has no module at {path}")
    key = "bench_family_" + name.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(key)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    missing = [n for n in FAMILY_NAMES if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"the family module {path} has no {missing}")
    return mod


def run_seeds(seed: int) -> tuple[int, int]:
    """(data seed, weight seed), 31 bits each, from a seed of any size."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(a) >> 1, int(b) >> 1


def peaks_for(kind: str) -> dict:
    table = _json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; peaks.json "
                       f"has {sorted(table)}")
    return table[kind]


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX found {len(devs)}")
    return devs


# -------------------------------------------------------- compile events
class CompileLog:
    """Backend compiles and compile-cache loads as ``(start, end)`` on
    ``time.time_ns``'s clock, and the programs requested (each built, or
    loaded from the persistent cache)."""

    def __init__(self):
        self.spans: list[tuple[int, int]] = []
        self.requests = 0

    def _duration(self, event, duration, **_):
        if event in (COMPILE_EVENT, CACHE_LOAD_EVENT):
            end = time.time_ns()
            self.spans.append((end - int(duration * 1e9), end))

    def _count(self, event, **_):
        if event == REQUEST_EVENT:
            self.requests += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._count)


# ---------------------------------------------------------- the program
class ProbedClients:
    """The task's client data as a sequence, recording the clients whose
    size the program asks for (the ``ClientStore`` size probe): one call
    per sampled client, in the order it trains them."""

    def __init__(self, shards):
        self._shards = shards
        self.asked: list[int] = []

    def __len__(self):
        return len(self._shards)

    def __getitem__(self, cid):
        return self._shards[int(cid)]

    def num_examples(self, cid) -> int:
        self.asked.append(int(cid))
        return len(self._shards[int(cid)][1])


def job_of(cell: dict) -> dict:
    """The FedSDD job: the mix's federation with the configuration's
    schedule (local epochs, KD steps)."""
    mix, cfg = cell["mix"], cell["config"]
    job = dict(mix["job"])
    job["num_clients"] = mix["population"]["num_clients"]
    job["local_epochs"] = cfg["local_epochs"]
    job["distill_steps"] = cfg["distill_steps"]
    return job


@dataclasses.dataclass
class Built:
    runner: object
    task: object
    clients: ProbedClients
    client_data: list
    server_x: np.ndarray
    sizes: list[int]
    job: dict
    schedule_seed: int
    weight_seed: int

    def schedule(self, t: int):
        """Round ``t``'s clients and minibatches (``schedule.py``)."""
        from schedule import round_schedule
        j = self.job
        return round_schedule(self.sizes, j["participation"], j["K"],
                              j["client_batch"], j["local_epochs"],
                              self.schedule_seed, t)


def build(cell: dict, seed: int, log=print) -> Built:
    """The cell's task and runner through the program's own entry points
    (``FedTask`` + ``make_runner``), with the family's data, task
    functions and initial weights."""
    from repro.core.fedsdd import FedConfig, FedTask, make_runner

    cfg, mix = cell["config"], cell["mix"]
    family = family_of(cfg)
    data_seed, weight_seed = run_seeds(seed)
    client_data, server_x = family.federation(cfg, mix, data_seed)
    clients = ProbedClients(client_data)
    task = FedTask(
        init_fn=family.make_init(cfg, weight_seed), client_data=clients,
        eval_fn=None,
        **family.program(cfg, server_x, mix["job"]["server_batch"]))
    job = job_of(cell)
    known = {f.name for f in dataclasses.fields(FedConfig)}
    pins = {}
    for k, v in mix["pins"].items():
        if k in known:
            pins[k] = v
        else:
            log(f"pin {k}={v!r} skipped: FedConfig has no such option")
    schedule_seed = mix["schedule_seed"]
    runner = make_runner("fedsdd", task, seed=schedule_seed, **job, **pins)
    return Built(runner, task, clients, client_data, server_x,
                 [len(y) for _, y in client_data], job, schedule_seed,
                 weight_seed)


def host_models(models) -> list:
    import jax
    return [jax.tree.map(lambda a: np.asarray(a, np.float32), m)
            for m in jax.device_get(list(models))]


def round_record(state) -> dict:
    rec = state.history[-1]
    return {"models": host_models(state.global_models),
            "kd_loss_first": rec.get("kd_loss_first"),
            "kd_loss_last": rec.get("kd_loss_last")}


def initial_models(cell: dict, b: Built) -> list:
    """The K initial models as the harness makes them, on the host: one
    key per group split from the job's seed, the run's weight seed folded
    in (the program's ``init_state`` passes the same keys to the task's
    ``init_fn``)."""
    import jax
    init = family_of(cell["config"]).make_init(cell["config"], b.weight_seed)
    keys = jax.random.split(jax.random.PRNGKey(b.schedule_seed), b.job["K"])
    return host_models([init(k) for k in keys])


def warm_up(cell: dict, b: Built, rounds: int, compiles=None, log=None):
    """The set-up's first rounds, through the window's own call on the
    window's runner and state: ``(state, start models, round records,
    whether the clients the program asked for are the schedule's, the
    shortest round's seconds less the compiles inside it)``."""
    import jax
    state = b.runner.init_state()
    start = initial_models(cell, b)
    records, agrees, steady = [], True, math.inf
    for t in range(1, rounds + 1):
        b.clients.asked.clear()
        n0 = len(compiles.spans) if compiles else 0
        w0, t0 = time.time_ns(), time.perf_counter()
        state = b.runner.run_round(state)
        jax.block_until_ready(state.global_models)
        seconds = time.perf_counter() - t0
        if compiles:
            seconds -= sum(min(e, time.time_ns()) - max(s, w0)
                           for s, e in compiles.spans[n0:]) / 1e9
        steady = min(steady, seconds)
        if log:
            log(f"warm-up round {t}: {time.perf_counter() - t0:.2f} s, "
                f"{seconds:.2f} s less compiles")
        agrees &= b.clients.asked == [r.cid for r in b.schedule(t)]
        records.append(round_record(state))
    return state, start, records, agrees, steady


def window_horizon(seconds: float, steady_round_s: float) -> int:
    """How many rounds a window of ``seconds`` can reach, with room: the
    rounds at two thirds of the shortest warm-up round, and one more."""
    if not math.isfinite(steady_round_s) or steady_round_s <= 0:
        return 1
    return int(math.ceil(1.5 * seconds / steady_round_s)) + 1


def warm_window_shapes(b: Built, state, horizon: int, log=print) -> int:
    """Compile every local-training shape that the next ``horizon`` rounds
    use and the rounds run so far did not: each round that brings a new
    shape runs once through ``run_round``, on a copy of the state (its own
    teacher bank, an empty history) that is then dropped.  Returns the
    rounds so run."""
    import jax
    from schedule import bucket_shapes
    done = state.round
    seen = {bucket_shapes(b.schedule(t)) for t in range(1, done + 1)}
    ran = 0
    for t in range(done + 1, done + 1 + horizon):
        shape = bucket_shapes(b.schedule(t))
        if shape in seen:
            continue
        seen.add(shape)
        scratch = dataclasses.replace(
            state, round=t - 1, ensemble=copy.deepcopy(state.ensemble),
            history=[])
        t0 = time.perf_counter()
        scratch = b.runner.run_round(scratch)
        jax.block_until_ready(scratch.global_models)
        del scratch
        ran += 1
        log(f"warmed round {t}'s shape {shape} in "
            f"{time.perf_counter() - t0:.1f} s")
    return ran


def reference_rounds(cell: dict, b: Built, start: list, rounds: int,
                     dtype=None) -> list[dict]:
    import jax.numpy as jnp
    from reference import Reference
    family = family_of(cell["config"])
    clients, teachers = family.REFERENCE_BLOCK
    ref = Reference(family.plain_model(cell["config"]), b.job,
                    dtype=jnp.float32 if dtype is None else dtype,
                    clients=clients, teachers=teachers)
    return ref.run(start, b.client_data, b.server_x, b.sizes,
                   b.schedule_seed, rounds)


# ------------------------------------------------------------ the run
def _metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    trace: object                    # trace.TraceSummary, or None
    records: list[dict]              # the window's round records
    round_seconds: list[float]       # host clock, per window round
    window_compiles: int             # programs built or loaded in the window
    setup_programs: int              # programs built or loaded in set-up
    round_flops: list[int]           # model FLOPs of each window round
    peaks: dict


@dataclasses.dataclass
class Session:
    """A run's result line and what the comparison was made from."""
    result: dict
    built: Built
    start: list            # the K initial models
    prog_rounds: list      # the program's rounds 1..warm+1, as compared
    ref_rounds: list       # the reference's, the same rounds
    values: dict           # the compared numbers
    reference_s: float     # the reference's seconds


def run(workload: str, seed: int, seconds: float, trace: bool, **kw) -> dict:
    """One run of a cell; returns the result line as a dict."""
    return session(workload, seed, seconds, trace, **kw).result


def session(workload: str, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, cell: dict | None = None,
            t_start: float | None = None, log=None, plant=None,
            warm_shapes: bool = True) -> Session:
    """One run of a cell.  The benchmark's tests pass a smaller ``cell``,
    skip the look for a chip, and ``plant(built)`` a fault in the program
    before its first round; ``calibrate.py``, which reads only the
    compared numbers, skips warming the window's shapes."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = cell or load_cell(workload)
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # sub-second programs too, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = require_chips(cell["chips"]) if require_chip else jax.devices()
    dev = devs[0]

    from check import judge, readings
    import device_trace as trace_lib

    warm = cell["mix"]["warmup_rounds"]
    with CompileLog() as compiles:
        b = build(cell, seed, log=log)
        log(f"built by {time.perf_counter() - t_start:.1f} s")
        if plant is not None:
            plant(b)
        state, start, prog_rounds, agrees, steady = warm_up(
            cell, b, warm, compiles, log)
        horizon = window_horizon(seconds, steady)
        log(f"warm-up: {warm} rounds by {time.perf_counter() - t_start:.1f}"
            f" s, steady round {steady:.2f} s, horizon {horizon} rounds")
        if warm_shapes:
            warm_window_shapes(b, state, horizon, log)
        finite = jax.jit(lambda ms: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(ms)])))
        finite(state.global_models).block_until_ready()
        setup_s = time.perf_counter() - t_start
        setup_programs = compiles.requests
        n_spans = len(compiles.spans)

        tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(tmp)
        flags, round_s, records = [], [], []
        limit = min(seconds, TRACE_SECONDS) if trace else seconds
        b.clients.asked.clear()
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            wall0 = time.time_ns()
            t0 = time.perf_counter()
            while True:
                r0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(trace_lib.ROUND_SPAN):
                    state = b.runner.run_round(state)
                    jax.block_until_ready(state.global_models)
                round_s.append(time.perf_counter() - r0)
                records.append(dict(state.history[-1]))
                flags.append(finite(state.global_models))
                if len(round_s) == 1:       # the round that is compared
                    first = (list(state.global_models),
                             list(b.clients.asked))
                if time.perf_counter() - t0 >= limit:
                    break
            window_s = time.perf_counter() - t0
        if trace:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace written in {time.perf_counter() - t1:.1f} s")
        window_programs = compiles.requests - setup_programs
        window_spans = compiles.spans[n_spans:]
    log(f"set-up {setup_s:.1f} s, {setup_programs} programs; window "
        f"{window_s:.2f} s, {window_programs} programs, rounds "
        + " ".join(f"{x:.3f}" for x in round_s))

    attempted = len(round_s)
    failed = sum(1 for f, rec in zip(flags, records)
                 if not bool(f) or not math.isfinite(
                     rec.get("kd_loss_last") or math.nan))
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    job, cfg = b.job, cell["config"]
    round_flops = [family_of(cfg).round_flops(
        cfg, job, b.schedule(warm + 1 + i), job["K"] * job["R"])
        for i in range(attempted)]
    prog_rounds.append({"models": host_models(first[0]),
                        "kd_loss_first": records[0].get("kd_loss_first"),
                        "kd_loss_last": records[0].get("kd_loss_last")})
    agrees &= first[1] == [r.cid for r in b.schedule(warm + 1)]
    del state, flags, first
    b.runner = None
    gc.collect()

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if trace:
        t1 = time.perf_counter()
        summary = trace_lib.summarize_dir(tmp, window_spans, wall0)
        shutil.rmtree(tmp, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t1:.1f} s")
        if summary is None and dev.platform == "tpu":
            raise RuntimeError("the trace holds no device operations")
        ctx = Context(trace=summary, records=records, round_seconds=round_s,
                      window_compiles=window_programs,
                      setup_programs=setup_programs,
                      round_flops=round_flops,
                      peaks=peaks_for(dev.device_kind) if dev.platform == "tpu"
                      else {})
        for metric in cell["per_layer"]:
            value = _metric_reader(metric["name"])(ctx)
            if value is not None:
                result["metrics"][metric["name"]] = {"value": value,
                                                     "unit": metric["unit"]}
        if summary is not None:
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": trace_lib.top_ops(summary),
                "idle_gaps": [[what, ns / 1e9]
                              for what, ns in summary.idle_gaps]}
    else:
        e2e = {"round_s": window_s / attempted,
               "peak_hbm_gib": peak / 2 ** 30,
               "setup_s": setup_s}
        for metric in cell["end_to_end"]:
            result["metrics"][metric["name"]] = {
                "value": e2e[metric["name"]], "unit": metric["unit"]}

    lim = cell["limits"]
    t_ref = time.perf_counter()
    ref_rounds = reference_rounds(cell, b, start, warm + 1)
    reference_s = time.perf_counter() - t_ref
    log(f"reference: {warm + 1} rounds in {reference_s:.1f} s")
    values = readings(start, prog_rounds, ref_rounds)
    ok, table = judge(values, lim["limits"] if lim else None)
    if not agrees:
        ok = False
        log("the clients the program trained are not the schedule's")
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = table
    return Session(result, b, start, prog_rounds, ref_rounds, values,
                   reference_s)
