"""One run of one cell: set-up, the measured window, the reference check.

The window drives the live ``repro.serving.engine.Engine`` through
``submit`` and ``step`` only, as a deployment does: unified role, paged
pool, the Pallas decode kernel, greedy sampling, a ``PrefixCache``, and
the scheduler's ``mixed`` knob on, so that every prefill runs in the
shape-stable ``_mixed_step`` and the window needs exactly two
executables, ``_mixed_step`` and ``_decode_paged``.

Token times come from the harness's clock after each ``Engine.step()``
returns; a step ends in the device-to-host read of its sampled tokens,
so they include the device's work.  Open-loop requests are timed from
the moment they were due.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from chipbench import correctness, generator, spec, weights, xplane

KERNEL = "paged_decode_attention"
TRACE_SECONDS = 6.0          # the traced part: the window's last seconds
WARM_REQUESTS = 2
WARM_NEW_TOKENS = 3


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class Track:
    """What the harness saw of one request."""
    req: object
    due: float
    session: int = -1
    admitted: float | None = None
    finished_at: float | None = None
    token_times: list[float] = field(default_factory=list)
    preempted: int = 0


@dataclass
class Step:
    kind: str
    t0: float
    t1: float
    decode_ctx: list[int]           # context of each live decode row
    prefill: tuple | None           # (start, valid tokens) of the chunk
    traced: bool


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: spec.Cell
    model: dict                     # model sizes as run
    engine: dict                    # engine sizes as run
    seconds: float
    window_s: float
    setup_s: float
    tracks: list[Track]
    steps: list[Step]
    cache_hits: int                 # PrefixCache tokens, window deltas
    cache_misses: int
    late_s: list[float]             # how late each submit ran
    trace: dict | None = None       # xplane.reduce(...)
    peak: dict | None = None        # flops.peaks(device_kind)


class CompileLog:
    """Executables built, as JAX's monitoring reports them: each
    compile-or-load (``names``, ``in_window``) and how many of them the
    persistent cache served (``cache_hits``)."""

    def __init__(self):
        import jax
        self.names: Counter = Counter()
        self.in_window: Counter = Counter()
        self.cache_hits = 0
        self.window = False

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                name = kw.get("fun_name", "?")
                self.names[name] += 1
                if self.window:
                    self.in_window[name] += 1

        def hit(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(hit)


def _engine(m_cfg, sc, params, seed):
    from repro.serving.engine import Engine
    from repro.serving.prefix_cache import PrefixCache
    eng = Engine(m_cfg, params, sc, name="bench", seed=seed)
    if eng.cache_layout != "paged":
        raise RuntimeError("the engine did not take the paged layout")
    cache = eng.attach_cache(PrefixCache(
        eng.scheduler.alloc, name="bench.cache", block_tokens=sc.page_size,
        clock=eng.now))
    return eng, cache


def _request(prompt: np.ndarray, max_new: int):
    from repro.core.types import Request
    return Request(prompt_len=len(prompt), max_new_tokens=int(max_new),
                   prompt_tokens=np.asarray(prompt, np.int32))


def _run_to_idle(eng) -> Counter:
    kinds: Counter = Counter()
    while eng.busy:
        kinds[eng.step().value] += 1
    return kinds


def warm_up(eng, cache, vocab: int, sc, seed: int) -> Counter:
    """Compile (or load) the window's two executables with requests whose
    tokens share nothing with the traffic, then empty the prefix cache."""
    rng = np.random.default_rng([seed, 1])
    n = min(2 * sc.page_size + 5, sc.max_context // 4)
    for _ in range(WARM_REQUESTS):
        eng.submit(_request(rng.integers(0, vocab, n), WARM_NEW_TOKENS))
    kinds = _run_to_idle(eng)
    cache.clear()
    return kinds


def prefill_setup(eng, prompts: list[np.ndarray]) -> None:
    """Prefill what the traffic needs resident before it starts (a shared
    system prompt, the sessions' histories) into the prefix cache: the
    first prompt alone, so that the others find it."""
    for group in (prompts[:1], prompts[1:]):
        for p in group:
            eng.submit(_request(p, 1))
        _run_to_idle(eng)


def drive(eng, cache, traffic, seconds: float, trace_dir: str | None
          ) -> tuple[list, list, float, list, int, int]:
    """The measured window.  Returns (tracks of the requests due in it,
    steps, window_s, late_s, cache hit tokens, cache miss tokens)."""
    import jax
    from repro.core.types import RequestState
    reqs, loop = traffic.requests, traffic.sessions
    pending = list(range(len(reqs)))        # not yet submitted, drawn order
    tracks: list[Track] = []
    steps: list[Step] = []
    late: list[float] = []
    hits0, miss0 = cache.hit_tokens, cache.miss_tokens
    trace_at = max(0.0, seconds - TRACE_SECONDS) if trace_dir else None
    tracing = None

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if tracing is not None
                else contextlib.nullcontext())

    def submit(now: float) -> list[int]:
        """Submit every request that is due; return those still held."""
        held, waiting = [], set()
        for k, i in enumerate(pending):
            r = reqs[i]
            if r.due > now:
                return held + pending[k:]
            if r.session >= 0:
                if r.session in waiting or not loop.ready(r.session):
                    waiting.add(r.session)       # its last turn is out
                    held.append(i)
                    continue
                due, prompt = loop.due(r), loop.prompt(r)
            else:
                due, prompt = r.due, r.prompt
            tr = Track(_request(prompt, r.max_new), due, r.session)
            tr.req.meta["bench"] = len(tracks)
            tracks.append(tr)
            eng.submit(tr.req)
            late.append(now - due)
        return held

    t_start = time.perf_counter()
    while True:
        now = time.perf_counter() - t_start
        if now >= seconds:
            break
        if trace_at is not None and tracing is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            tracing = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            tracing.__enter__()
        with span("submit"):
            pending = submit(now)
        if not eng.busy:
            # nothing in flight, so no session is waiting on an answer
            wake = min(reqs[pending[0]].due if pending else seconds, seconds)
            with span("wait"):
                time.sleep(max(0.0, wake - now))
            continue
        before = [(r, r.state, r.generated, r.prefilled)
                  for r in eng.scheduler.running]
        n_fin = len(eng.finished)
        t0 = time.perf_counter() - t_start
        with span(f"step#{len(steps)}"):
            kind = eng.step()
        t1 = time.perf_counter() - t_start
        with span("bookkeeping"):
            seen = {id(r) for r, *_ in before}
            after = [r for r in eng.scheduler.running if id(r) not in seen]
            after += [r for r in eng.finished[n_fin:] if id(r) not in seen]
            decode_ctx, prefill = [], None
            for r, state, gen, pref in before:
                if state == RequestState.RUNNING and r.generated > gen:
                    decode_ctx.append(r.prompt_len + gen)
                elif r.prefilled > pref:
                    prefill = (pref, r.prefilled - pref)
            for r in after:
                pref = r.meta.get("cached_prompt_tokens", 0)
                if r.prefilled > pref:
                    prefill = (pref, r.prefilled - pref)
            for r in [b[0] for b in before] + after:
                i = r.meta.get("bench")
                if i is None:
                    continue
                tr = tracks[i]
                if tr.admitted is None:
                    tr.admitted = t1
                got = len(r.output_tokens)
                if got < len(tr.token_times):       # preempted: restarts
                    tr.preempted += 1
                    del tr.token_times[got:]
                tr.token_times.extend([t1] * (got - len(tr.token_times)))
                if (r.state == RequestState.FINISHED
                        and tr.finished_at is None):
                    tr.finished_at = t1
                    if tr.session >= 0:
                        loop.answered(tr.session, r.prompt_tokens,
                                      r.output_tokens, t1)
            steps.append(Step(kind.value, t0, t1, decode_ctx, prefill,
                              tracing is not None))
    window_s = time.perf_counter() - t_start
    if tracing is not None:
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return (tracks, steps, window_s, late,
            cache.hit_tokens - hits0, cache.miss_tokens - miss0)


def finished(tracks: list[Track]) -> list[tuple[np.ndarray, list[int]]]:
    from repro.core.types import RequestState
    return [(np.asarray(t.req.prompt_tokens), list(t.req.output_tokens))
            for t in tracks
            if t.req.state == RequestState.FINISHED
            and len(t.req.output_tokens) == t.req.max_new_tokens]


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool, t_process: float,
        control: bool = False):
    """One run.  Returns (result dict for the last line, compared).
    ``control`` (calibration only, never the benchmark's own runs) also
    reads the reference computed in fp8, the control, in the control's
    place: the readings go under ``"reading"`` and the same comparison's
    verdict on the control under ``"control_correct"``."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from chipbench import flops

    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileLog()
    model, engine = spec.sizes(cell.config, rehearse)
    m_cfg = spec.model_config(cell.config, rehearse)
    sc = spec.scheduler_config(cell.config, rehearse)
    mix = cell.mix
    if rehearse:
        mix = generator.scaled(mix, engine["max_context"]
                               / cell.config["engine"]["max_context"])
    traffic = generator.generate(mix, model["vocab"], engine["max_context"],
                                 seconds, seed)
    dev = jax.devices()[0]

    canon = weights.make(model, seed)
    params = weights.to_program(canon, m_cfg)
    jax.block_until_ready(params)
    eng, cache = _engine(m_cfg, sc, params, seed)
    kinds = warm_up(eng, cache, model["vocab"], sc, seed)
    log(f"warm-up steps: {dict(kinds)}")
    prefill_setup(eng, traffic.setup)
    log(f"set-up prefill: {len(traffic.setup)} prompts, "
        f"{sum(len(p) for p in traffic.setup)} tokens, prefix cache "
        f"{cache.blocks_resident} blocks")
    log(f"set-up compiles or loads: {sum(compiles.names.values())}, "
        f"{compiles.cache_hits} from the persistent cache: "
        f"{dict(compiles.names)}")
    if any("_prefill_paged" in k for k in compiles.names):
        raise RuntimeError("_prefill_paged compiled: the window would "
                           "serialize prefill")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    compiles.window = True
    setup_s = time.perf_counter() - t_process
    log(f"setup_s: {setup_s}")
    tracks, steps, window_s, late, hits, misses = drive(
        eng, cache, traffic, seconds, trace_dir)
    compiles.window = False
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    log(f"window: {window_s:.3f} s, {len(tracks)} requests due, "
        f"{sum(len(t.token_times) for t in tracks)} tokens, steps "
        f"{dict(Counter(s.kind for s in steps))}, "
        f"mixed_step_traces {eng.mixed_step_traces}")
    log(f"window compiles or loads: {sum(compiles.in_window.values())} "
        f"{dict(compiles.in_window)}")
    if steps:
        # a host stall shows as a step, or a gap between steps (idle
        # waits for arrivals included), far longer than its kind's median
        slow = sorted(steps, key=lambda s: s.t1 - s.t0)[-3:][::-1]
        gaps = sorted(b.t0 - a.t1 for a, b in zip(steps, steps[1:]))[-3:]
        log("longest steps: " + ", ".join(
            f"{s.kind} {s.t1 - s.t0:.3f} s at {s.t0:.1f} s" for s in slow)
            + "; longest gaps between steps: "
            + ", ".join(f"{g:.3f} s" for g in gaps[::-1]))
    if late:
        log(f"generator lateness: p50 {np.median(late) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} submits")
    pre = sum(t.preempted for t in tracks)
    log(f"preemptions: {pre}; prefix cache window hits {hits} misses "
        f"{misses}; evictions {cache.evictions}")

    done = finished(tracks)
    attempted = len(tracks)
    failed = sum(1 for t in tracks if t.req.state.value == "failed")
    # free the program's state before the reference runs
    eng.cache = None
    eng.params = None
    del params, canon
    gc.collect()

    reading = correctness.check(cell.config, model, seed, done, control)
    limit = spec.limit(cell.config, rehearse)
    ok, compared = correctness.verdict(reading, limit)
    log(f"reference: {reading['requests']} requests, {reading['tokens']} "
        f"served tokens compared")

    r = Run(cell, model, engine, seconds, window_s, setup_s, tracks, steps,
            hits, misses, late)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": ok, "attempted": attempted, "failed": failed}
    if control:
        out["reading"] = reading
        out["control_correct"], _ = correctness.verdict(
            {**reading, "max_logit_gap": reading.get("control_max_logit_gap")},
            limit)
    if rehearse:
        out.update(rehearsal=True, device={"platform": dev.platform,
                                           "count": len(jax.devices())},
                   counts={"steps": dict(Counter(s.kind for s in steps)),
                           "tokens": sum(len(t.token_times) for t in tracks),
                           "window_compiles": sum(compiles.in_window.values()),
                           "mixed_step_traces": eng.mixed_step_traces})
        return out, compared
    r.peak = flops.peaks(dev.device_kind)
    if trace:
        kinds_by_step = {f"step#{i}": f"step.{s.kind}"
                         for i, s in enumerate(steps)}

        def label(name):
            base = xplane.harness_span(name)
            return kinds_by_step.get(base, base) if base else None

        red = xplane.reduce(xplane.load(xplane.find(trace_dir)),
                            kernels=(KERNEL,), labels=label)
        r.trace = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        kind = "layer_metrics" if trace else "end_to_end"
        v = spec.reader(kind, m["name"]).read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    if trace:
        out["breakdown"] = {"device_ops": r.trace["device_ops"],
                            "idle_gaps": r.trace["idle_gaps"]}
    return out, compared
