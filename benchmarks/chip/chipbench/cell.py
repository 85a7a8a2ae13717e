"""One run of one cell, in the process that holds the chip.

The path the window drives is the program's own, through its public
API: ``launch/server.py``'s ``HttpFrontendServer`` over ``AsyncFrontend``
over ``ServingEngine`` (scheduler, ``Executor.unified_step``, the Pallas
paged-attention kernel), fed over a real socket by ``loadgen.py`` in a
child process.  The harness changes no program code: it builds the
engine from the configuration and traffic files, hands it the weights
it made, and wraps the engine instance's ``plan``, ``execute`` and
``commit`` and the front door's ``pump`` in spans of its own
(``chipbench.<name>``, also ``jax.profiler.TraceAnnotation``s).

Order of a run: weights made on the device, engine built, every
(tokens, pages) bucket that the traffic can reach run once (compiled,
or loaded from the persistent cache), the server started, the load
generator's warm-up, the measured window, the drain; then the program's
state is freed and the reference decides ``correct``.
"""

from __future__ import annotations

import asyncio
import gc
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import check, flops, spec, stats, tracefile, traffic as traffic_mod
from .weights import Dims, Float32Weights, program_params

LOADGEN = Path(__file__).resolve().parent / "loadgen.py"
HOST = "127.0.0.1"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def devices(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# the program under test

def lm_config(config: dict):
    """The program's ``LMConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.models.lm import BlockSpec, LMConfig
    c, s = config, config["serve"]
    if c["hidden_act"] != "silu" or c.get("sliding_window") is not None:
        raise spec.SpecError("the dense_gqa path serves silu, full "
                             "attention")
    return LMConfig(
        name=c["name"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or
        c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        pattern=(BlockSpec("attn", "dense"),),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        act="silu", tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype=jnp.dtype(s["dtype"]), remat="none",
        attn_backend=s["attn_backend"])


def max_pages(config: dict, traffic: dict) -> int:
    """Pages of the longest request the traffic sends."""
    n = (traffic_mod.max_length(traffic["prompt_tokens"])
         + traffic_mod.max_length(traffic["output_tokens"]))
    return -(-n // config["serve"]["page_size"])


def build_engine(config: dict, traffic: dict, seed: int):
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams
    e = traffic["engine"]
    params = program_params(Dims.of(config), seed)
    engine = ServingEngine(
        lm_config(config), params,
        page_size=config["serve"]["page_size"],
        num_pages=config["serve"]["num_pages"],
        max_batch=e["max_batch"], chunk_size=e["chunk_size"],
        max_pages_per_seq=max_pages(config, traffic),
        sampling=SamplingParams(temperature=float(e["temperature"]),
                                top_p=float(e["top_p"]),
                                seed=int(e["seed"])))
    del params          # the executor keeps its own per-layer copy
    return engine


def reachable_buckets(engine, config: dict, traffic: dict
                      ) -> List[Tuple[int, int]]:
    """The (tokens, pages) buckets a step of this traffic can take:
    every token bucket, and every pages bucket from that of the
    shortest prompt (admission gives a request pages for its whole
    prompt) to that of the longest request."""
    sched = engine.scheduler
    shortest = -(-traffic_mod.min_length(traffic["prompt_tokens"])
                 // config["serve"]["page_size"])
    lo = next(p for p in sched.p_buckets() if p >= shortest)
    return [(t, p) for t in sched.t_buckets()
            for p in sched.p_buckets() if p >= lo]


def warm(engine, buckets: Sequence[Tuple[int, int]]) -> None:
    """Run one all-padding step in each bucket, so that each is compiled
    (or loaded) before the window.  Padding writes no K/V and reads
    only page 0."""
    from repro.serving.scheduler import StepPlan
    s, kp1 = engine.max_batch, engine.spec_k + 1
    oob = engine.kv.pages_per_replica * engine.kv.page_size
    for t, p in buckets:
        plan = StepPlan(
            spans=[], slot_seqs=[-1] * s,
            tokens=np.zeros(t, np.int32), seg_ids=np.full(t, -1, np.int32),
            positions=np.zeros(t, np.int32),
            write_idx=np.full(t, oob, np.int32),
            sample_idx=np.zeros((s, kp1), np.int32),
            sample_pos=np.zeros(s, np.int32),
            temps=np.zeros(s, np.float32), top_ks=np.zeros(s, np.int32),
            top_ps=np.ones(s, np.float32), seeds=np.zeros(s, np.uint32),
            n_tokens=0, t_bucket=t, p_bucket=p)
        engine.executor.execute(plan, engine.kv)


# ---------------------------------------------------------------------------
# spans and counters

@dataclass
class Log:
    """What the harness's spans and listeners saw, on the server's
    ``time.perf_counter`` clock."""
    plan: List[Tuple[float, float]] = field(default_factory=list)
    commit: List[Tuple[float, float]] = field(default_factory=list)
    # (t0, t1, t_bucket, p_bucket, [(start, end, sampled), ...])
    steps: List[tuple] = field(default_factory=list)
    compiles: List[Tuple[float, str]] = field(default_factory=list)


def _span(name: str, fn, sink: Optional[list] = None):
    import jax
    label = f"{tracefile.SPAN_PREFIX}{name}"

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            t1 = time.perf_counter()
        if sink is not None:
            sink.append((t0, t1))
        return out
    return wrapped


def instrument(engine, frontend, log: Log) -> None:
    """Wrap the instance's calls into each layer in the harness's spans
    (the program's code is untouched)."""
    sched, ex = engine.scheduler, engine.executor
    sched.plan = _span("plan", sched.plan, log.plan)
    sched.commit = _span("commit", sched.commit, log.commit)
    execute = _span("execute", ex.execute)

    def logged_execute(plan, kv):
        t0 = time.perf_counter()
        out = execute(plan, kv)
        log.steps.append((t0, time.perf_counter(), plan.t_bucket,
                          plan.p_bucket,
                          [(s.start, s.end + len(s.drafts), s.sample)
                           for s in plan.spans]))
        return out
    ex.execute = logged_execute
    frontend.pump = _span("pump", frontend.pump)


def listen_compiles(log: Log) -> None:
    """Count every lowering to XLA (a jit cache miss, whether the
    executable then compiles or loads from the persistent cache)."""
    import jax

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            log.compiles.append((time.perf_counter(),
                                 str(kw.get("fun_name", ""))))
    jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# the served window

@dataclass
class Served:
    result: dict                  # loadgen's RESULT
    marks: Dict[str, float]       # unix and perf_counter times of the
                                  # window's start and end, server side
    trace_s: Optional[Tuple[float, float]] = None


async def serve(engine, traffic: dict, seed: int, vocab: int,
                seconds: float, trace_dir: Optional[Path],
                log: Log) -> Served:
    import jax
    from repro.launch.server import HttpFrontendServer
    from repro.serving.frontend import AsyncFrontend
    srv = traffic["server"]
    fe = AsyncFrontend(engine, hwm_frac=float(srv["hwm_frac"]),
                       max_queue_depth=int(srv["max_queue_depth"]),
                       max_stream_tokens=int(srv["max_stream_tokens"]))
    instrument(engine, fe, log)
    server = HttpFrontendServer(fe, HOST, 0)
    await server.start()
    plan = {"host": HOST, "port": server.port,
            "warmup_s": traffic["warmup_s"], "window_s": seconds,
            "drain_s": traffic["drain_s"], "traffic": traffic,
            "seed": seed, "vocab": vocab}
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(LOADGEN), stdin=asyncio.subprocess.PIPE,
        stdout=asyncio.subprocess.PIPE, limit=1 << 30)
    marks: Dict[str, float] = {}
    trace_s = None
    result = None
    try:
        proc.stdin.write(json.dumps(plan).encode())
        await proc.stdin.drain()
        proc.stdin.close()
        while True:
            line = (await proc.stdout.readline()).decode()
            if not line:
                break
            word, _, rest = line.partition(" ")
            if word == "WINDOW_START":
                marks["start_unix"] = float(rest)
                marks["start"] = time.perf_counter()
                if trace_dir is not None:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    t_trace = time.perf_counter()
            elif word == "WINDOW_END":
                marks["end_unix"] = float(rest)
                marks["end"] = time.perf_counter()
                if trace_dir is not None:
                    jax.profiler.stop_trace()
                    trace_s = (t_trace, marks["end"])
            elif word == "RESULT":
                result = json.loads(rest)
        if await proc.wait() != 0 or result is None:
            raise RuntimeError(f"load generator exited {proc.returncode} "
                               f"without a result")
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        await server.stop()        # raises the engine's error, if any
    return Served(result=result, marks=marks, trace_s=trace_s)


# ---------------------------------------------------------------------------
# what the metric readers see

@dataclass
class Run:
    """Everything a per-layer metric reader may read."""
    cell: str
    config: dict
    traffic: dict
    model: flops.Model
    peaks: dict
    records: List[dict]               # loadgen records + max_new_tokens
    window: Tuple[float, float]       # client clock
    log: Log
    host_window: Tuple[float, float]  # server perf_counter
    trace: Optional[tracefile.Trace] = None

    def steps_in_window(self) -> List[tuple]:
        a, b = self.host_window
        return [s for s in self.log.steps if s[0] >= a and s[1] <= b]

    def compiles_in_window(self) -> List[Tuple[float, str]]:
        a, b = self.host_window
        return [c for c in self.log.compiles if a <= c[0] <= b]


class MissingMetric(RuntimeError):
    """A metric whose ``workloads`` name this cell found nothing to read."""


def per_layer(run: Run, metrics: Sequence[dict],
              harness_dir: Path = spec.HARNESS_DIR) -> Dict[str, dict]:
    """Each metric's reader, found by name.  A reader that finds nothing
    to read returns None and its metric is left out; where the metric's
    ``workloads`` name this cell, that is an error, not a quiet gap."""
    out, missing = {}, []
    for m in metrics:
        v = spec.reader(m["name"], harness_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        elif run.cell in m.get("workloads", ()):
            missing.append(m["name"])
    if missing:
        raise MissingMetric(f"{run.cell}: nothing to read for "
                            f"{', '.join(missing)}, which BENCHMARK.json "
                            f"lists for this cell")
    return out


def device_times(trace: tracefile.Trace) -> Tuple[float, float]:
    """(busy_s averaged over the chips, window_s)."""
    busy = [tracefile.busy_ns(trace.ops[c], trace.window)
            for c in trace.chips]
    return (sum(busy) / len(busy) / 1e9 if busy else 0.0,
            (trace.window[1] - trace.window[0]) / 1e9)


def breakdown(trace: tracefile.Trace) -> dict:
    ops = [e for c in trace.chips for e in trace.ops[c]]
    gaps = [g for c in trace.chips
            for g in tracefile.idle_gaps(trace.ops[c], trace.window)]
    return {"device_ops": [list(x) for x in tracefile.top_ops(ops, 10)],
            "idle_gaps": [list(x) for x in
                          tracefile.attribute_gaps(gaps, trace.spans)[:10]]}


def steps_by_bucket(steps: Sequence[tuple]) -> Dict[str, list]:
    """Steps of the window by (tokens, pages) bucket: count and mean host
    milliseconds of ``execute``."""
    out: Dict[str, list] = {}
    for t0, t1, tb, pb, _ in steps:
        n, ms = out.get(f"{tb}x{pb}", [0, 0.0])
        out[f"{tb}x{pb}"] = [n + 1, ms + (t1 - t0) * 1e3]
    return {k: [n, ms / n] for k, (n, ms) in sorted(out.items())}


# ---------------------------------------------------------------------------
# one run

def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, work_dir: Path, require_chip: bool = True,
             controls: Sequence[str] = (), peaks: Optional[dict] = None, log_to=sys.stderr) -> dict:
    """Run ``cell`` once; return the result line's object.  Off the
    chip (tests) ``require_chip=False`` and ``peaks`` stand in."""
    def say(msg: str) -> None:
        print(f"[chipbench] {time.time() - t_start:8.2f}s {msg}",
              file=log_to, flush=True)

    dev = devices(cell.chips, require_chip)
    config, traffic = cell.config, cell.traffic
    if peaks is None:
        peaks = spec.peaks(dev["kind"]) if trace else {}
    log = Log()
    listen_compiles(log)
    engine = build_engine(config, traffic, seed)
    say(f"engine built: {config['name']} on {dev['kind']}")
    buckets = reachable_buckets(engine, config, traffic)
    warm(engine, buckets)
    say(f"warmed {len(buckets)} (tokens, pages) buckets")
    trace_dir = work_dir / "trace" if trace else None
    served = asyncio.run(serve(engine, traffic, seed, config["vocab_size"],
                               seconds, trace_dir, log))
    res = served.result
    records = res["records"]
    # the requests the load generator drew, drawn again from the seed
    reqs = traffic_mod.generate(
        traffic, seed, config["vocab_size"], seconds,
        count=1 + max((r["i"] for r in records), default=-1))
    for r in records:
        r["max_new_tokens"] = reqs[r["i"]].max_new_tokens
    window = tuple(res["window"])
    setup_s = served.marks["start_unix"] - t_start
    peak = memory_peak_bytes()
    n_compiles = engine.executor.compile_count
    say(f"window closed; {len(records)} requests, {n_compiles} step "
        f"programs, peak {peak} bytes")

    run = Run(cell=cell.name, config=config, traffic=traffic,
              model=flops.Model.of(config), peaks=peaks, records=records,
              window=window, log=log,
              host_window=(served.marks["start"], served.marks["end"]))
    steps = run.steps_in_window()
    device = dict(dev, memory_peak_bytes=peak)
    extra = {}
    if trace:
        run.trace = tracefile.load(str(trace_dir),
                                   served.trace_s[1] - served.trace_s[0])
        metrics = per_layer(run, cell.per_layer)
        busy_s, window_s = device_times(run.trace)
        device.update(busy_s=busy_s, window_s=window_s)
        extra["breakdown"] = breakdown(run.trace)
    else:
        wanted = [m["name"] for m in cell.end_to_end]
        vals = stats.end_to_end(records, window, res["end"], wanted)
        vals["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(vals[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}

    # free the program's state before the reference runs on the chip
    del engine, run
    gc.collect()
    prompts = [r.prompt for r in reqs]
    picked = check.sample(records, prompts, seed,
                          int(cell.limits["sample_requests"]))
    values = {}
    if picked:
        values = check.readings(
            spec.reference(config), config, Float32Weights(
                Dims.of(config), seed), picked, prompts,
            traffic["engine"], controls)
    say(f"reference compared {values.get('tokens_compared', 0)} tokens")
    unfinished = check.unfinished(records)
    checks = check.verdict(values, cell.limits, unfinished)
    sent = stats.sent_in_window(records, window)
    out = {"correct": check.passed(checks), "attempted": len(sent),
           "failed": sum(1 for r in sent if stats.failed(r)),
           "metrics": metrics, "device": device, **extra,
           "setup": {"setup_s": setup_s, "buckets_warmed": len(buckets)},
           "loadgen": stats.lateness(records, window),
           "requests_sent": len(records),
           "steps_in_window": steps_by_bucket(steps),
           "readings": {k: v for k, v in values.items()
                        if k != "logit_gap"}}
    if controls:
        # each control through the same verdict as the program
        out["controls"] = {
            mode: {"correct": check.passed(c),
                   "logit_gap": c["logit_gap"]["value"]}
            for mode, c in check.control_checks(
                values, cell.limits, unfinished).items()}
    out["checks"] = checks
    return out
