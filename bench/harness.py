"""Run one cell of the benchmark once: set up, measure, check, report.

The front end is a loop around the engine's public calls.  Each
iteration admits whatever is due (``BatchedEngine.admit``, which ends in
the first tokens' transfer), or else runs one decode tick and hands its
tokens to their users (``step`` then ``sync``), or else waits for the
next request to fall due.  Times are taken on the host clock when tokens
reach the host.  Set-up warms up every shape the window will use, so
that nothing compiles inside it; compilations inside the window are
counted all the same.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import tempfile
import time
from collections import deque
from typing import List, Optional

import numpy as np

from bench import check, e2e, roofline, spec, traffic, tracing

#: most requests handed to one ``admit`` call (each size is warmed up)
ADMIT_BATCH = 4
#: requests due in the window are followed this long past its close
DRAIN_CAP_S = 120.0
#: the jitted programs the trace reduction attributes device time to
MODULES = {"tick": "_tick_impl", "prefill": "_prefill_one_impl"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JIT_EVENT_PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")
#: the gap a run reads when it finished no request to compare
NOTHING_COMPARED = 1e30


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# configuration -> the program's objects
# ---------------------------------------------------------------------------

#: configuration-file key -> the program's ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the program's
    own entry for ``program_arch``, with every size the file states."""
    import dataclasses as dc
    from repro import configs
    base = configs.get_config(cfg["program_arch"])
    fields = {f: cfg[k] for k, f in _FIELDS.items() if k in cfg}
    return dc.replace(base, name=cfg["name"], **fields)


def parallel_config():
    """The program's default policy."""
    from repro.models.config import ParallelConfig
    return ParallelConfig(remat="none")


def pool_pages(cfg: dict, slots: int, max_seq: int, device) -> int:
    """Pages that fit twice in what the weights leave (each admission
    copies a whole K or V pool outside jit), less the configuration's
    headroom for activations, capped at the slots' dense equivalent."""
    serve = cfg["serve"]
    ps = serve["page_size"]
    dense = slots * -(-max_seq // ps)
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return dense
    free = stats["bytes_limit"] - stats["bytes_in_use"] \
        - serve["headroom_bytes"]
    page = roofline.kv_bytes_per_token(cfg) * ps
    pages = min(free // (2 * page), dense)
    if pages < -(-max_seq // ps):
        raise RuntimeError(f"no room for one request's pages: {free} bytes "
                           f"free beside the weights")
    return int(pages)


# ---------------------------------------------------------------------------
# the front end
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Entry:
    """A request of the window: what was sent, and what came back."""

    req: object          # the engine's Request
    rec: e2e.Record
    spec: traffic.Req

    @property
    def rid(self):
        return self.rec.rid

    @property
    def generated(self):
        return self.req.generated

    @property
    def shared_pages(self):
        return self.rec.shared_pages


class FrontEnd:
    """Drives an engine through ``admit`` / ``step`` / ``sync``."""

    def __init__(self, engine, page_size: int):
        import jax
        self.engine = engine
        self.page_size = page_size
        self.ann = jax.profiler.TraceAnnotation
        self.active: dict = {}
        self.queue: deque = deque()
        self.blocked = False
        self.t0 = 0.0
        self.window_s = float("inf")
        # what the per-layer readers take, for the window only
        self.admit_calls: List[tuple] = []
        self.prefill_lens: List[int] = []
        self.tick_work: List[tuple] = []
        self.shared_hits = 0
        self.prompt_pages = 0
        self.longest = {"admit": 0.0, "tick": 0.0}
        self.in_window = True

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _free_slot(self) -> bool:
        return any(r is None or r.done for r in self.engine.slots)

    def admit(self) -> int:
        batch = [self.queue[i] for i in range(min(ADMIT_BATCH,
                                                  len(self.queue)))]
        pool = self.engine.pool
        hits0 = pool.shared_hits if pool is not None else 0
        t_a = self.now()
        with self.ann("bench.admit"):
            consumed = self.engine.admit([e.req for e in batch])
        t_b = self.now()
        hits = (pool.shared_hits if pool is not None else 0) - hits0
        admitted = 0
        for e in batch[:consumed]:
            self.queue.popleft()
            if e.req.rejected:
                e.rec.failed = True
                e.rec.done = True
                continue
            admitted += 1
            e.rec.admit_start = t_a
            e.rec.shared_pages = hits
            e.rec.deliver(t_b, len(e.req.generated), self.window_s)
            if self.in_window:
                self.prefill_lens.append(len(e.req.prompt))
                self.prompt_pages += len(e.req.prompt) // self.page_size
            if e.req.done:
                e.rec.done = True
            else:
                self.active[e.rid] = e
        if self.in_window:
            self.admit_calls.append((t_a, t_b, admitted))
            self.shared_hits += hits
            self.longest["admit"] = max(self.longest["admit"], t_b - t_a)
        if consumed < len(batch):
            self.blocked = True
        return consumed

    def tick(self, cfg: Optional[dict]) -> None:
        t_a = self.now()
        if self.in_window and cfg is not None:
            ctx = [len(e.req.prompt) + len(e.req.generated)
                   for e in self.active.values()]
            self.tick_work.append(roofline.decode_step(cfg, ctx))
        with self.ann("bench.step"):
            self.engine.step()
        with self.ann("bench.sync"):
            self.engine.sync()
        t = self.now()
        if self.in_window:
            self.longest["tick"] = max(self.longest["tick"], t - t_a)
        for rid in list(self.active):
            e = self.active[rid]
            e.rec.deliver(t, len(e.req.generated) - e.rec.n, self.window_s)
            if e.req.done:
                e.rec.done = True
                del self.active[rid]
                self.blocked = False

    def loop(self, pending: deque, until: float, admit_new: bool,
             cfg: Optional[dict], wait: bool = True) -> None:
        """Serve until ``until`` (seconds from ``t0``), or until nothing is
        left to do when ``wait`` is False."""
        while True:
            now = self.now()
            if now >= until:
                return
            while admit_new and pending and pending[0].spec.due_s <= now:
                e = pending.popleft()
                e.rec.queued = now
                self.queue.append(e)
            if self.queue and not self.blocked and self._free_slot():
                self.admit()
                continue
            if self.active:
                self.tick(cfg)
                continue
            if not wait and not self.queue:
                return
            if admit_new and pending:
                nxt = min(pending[0].spec.due_s, until)
                with self.ann("bench.wait"):
                    time.sleep(max(nxt - self.now(), 0.0))
                continue
            if not wait:
                return
            time.sleep(max(min(until - self.now(), 0.01), 0.0))


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------

def warm_up(engine, mix: dict, vocab: int) -> int:
    """Run every shape the window can use through the engine: each prompt
    length (prefill and page scatter), with and without a shared document
    where the mix shares one, each admission batch size, and the tick.
    Returns the number of warm-up requests."""
    from repro.serve import Request
    rng = np.random.default_rng(0)
    toks = lambda n: rng.integers(2, vocab, int(n)).tolist()
    batches = [[toks(n)] for n in traffic.prompt_lengths(mix)]
    docs = mix.get("documents")
    if docs is not None and docs.get("shared", True):
        for d in traffic.grid(docs["length"]):
            body = toks(d)
            for q in traffic.grid(docs["question"]):
                batches.append([body + toks(q), body + toks(q)])
    short = min(traffic.prompt_lengths(mix))
    batches += [[toks(short) for _ in range(k)]
                for k in range(1, ADMIT_BATCH + 1)]
    n = 0
    for prompts in batches:
        reqs = [Request(rid=-1 - n - i, prompt=p, max_new_tokens=2)
                for i, p in enumerate(prompts)]
        n += len(reqs)
        left = list(reqs)
        while left:
            k = engine.admit(left)
            del left[:k]
            while any(r is not None and not r.done for r in engine.slots):
                engine.step()
                engine.sync()
    return n


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerCtx:
    """What a per-layer reader may read of one run."""

    cfg: dict
    records: list
    window_s: float
    give_up: float
    admit_calls: list
    prefill_lens: list
    tick_work: list
    tick_stats: list
    shared_hits: int
    prompt_pages: int
    trace: Optional[dict]
    peak: Optional[dict]


def _devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devices)}")
    return devices


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             repo: str = spec.REPO, root: str = spec.ROOT,
             process_start: Optional[float] = None,
             rate: Optional[float] = None,
             schedule_seed: Optional[int] = None,
             control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``rate`` and ``schedule_seed`` replace the mix's arrival rate and
    schedule, and ``control`` adds the float8 control's reading to the
    checks: all three serve the calibration (``bench/calibrate.py``),
    never the benchmark's own runs."""
    process_start = process_start or time.perf_counter()
    bench = spec.load_benchmark(repo)
    cell = spec.workload(name, bench)
    cfg = spec.load_config(cell["config"], root)
    mix = spec.load_traffic(cell["traffic"], root)
    if rate is not None:
        mix["rate_per_s"] = rate
    if schedule_seed is not None:
        mix["schedule_seed"] = schedule_seed

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = _devices(cell["chips"], require_tpu)
    device = devices[0]
    from repro.models import build_model
    from repro.serve import BatchedEngine, Request, ServeConfig
    from bench.weights import make_params

    mcfg = model_config(cfg)
    model = build_model(mcfg, parallel_config())
    t = time.perf_counter()
    params = make_params(model, seed)
    jax.block_until_ready(params)
    log(f"weights: {sum(x.size for x in jax.tree.leaves(params))} "
        f"parameters in {time.perf_counter() - t:.3f}s")
    vocab = cfg["vocab_size"]
    schedule = traffic.generate(mix, seconds, seed, vocab)
    serve = cfg["serve"]
    ps = serve["page_size"]
    slots = serve["batch_slots"]
    max_seq = traffic.longest(mix)
    pages = pool_pages(cfg, slots, max_seq, device)
    engine = BatchedEngine(model, params, ServeConfig(
        batch_slots=slots, max_seq_len=max_seq, eos_id=-1, page_size=ps,
        num_pages=pages))
    log(f"engine: {slots} slots, {pages} pages of {ps} tokens, max_seq "
        f"{max_seq}")
    t = time.perf_counter()
    n_warm = warm_up(engine, mix, vocab)
    jax.block_until_ready(engine.cache)
    log(f"warm-up: {n_warm} requests in {time.perf_counter() - t:.3f}s")

    # every step of JAX's compile path (tracing, lowering, the persistent
    # cache, the backend compile) that runs inside the window
    jit_events: dict = {}

    def on_event(event, duration, **kw):
        if event.startswith(JIT_EVENT_PREFIXES):
            n, total = jit_events.get(event, (0, 0.0))
            jit_events[event] = (n + 1, total + duration)

    gc_pauses: List[float] = []
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            gc_pauses.append(time.perf_counter() - gc_start[0])

    entries = [Entry(Request(rid=r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new),
                     e2e.Record(r.rid, r.due_s, len(r.prompt), r.max_new),
                     r) for r in schedule]
    backlog = mix["kind"] == "backlog"
    if not backlog:
        entries = [e for e in entries if e.spec.due_s < seconds]
    pending = deque(entries)
    fe = FrontEnd(engine, ps)
    fe.window_s = seconds
    tick0 = engine.tick_count
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        fe.t0 = time.perf_counter()
        setup_s = fe.t0 - process_start
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            fe.loop(pending, seconds, admit_new=True, cfg=cfg)
        in_window_compiles = jit_events.get(COMPILE_EVENT, (0, 0.0))[0]
    finally:
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_duration_listener(on_event)
        # the profiler slows the host more the longer it runs: a traced
        # drain ran 2.6 times as long as an untraced one on one v5e
        if trace:
            jax.profiler.stop_trace()
    tick1 = engine.tick_count
    queue_at_close = len(fe.queue) + sum(
        1 for e in pending if e.spec.due_s < seconds)
    fe.in_window = False
    if not backlog:
        # due in the window, but the loop was inside a call at the close
        fe.queue.extend(pending)
        for e in pending:
            e.rec.queued = fe.now()
    # the drain: follow what was attempted in the window to its end
    if backlog:
        attempted = [e for e in entries if e.rec.admit_start is not None
                     or e.rec.failed]
        fe.queue.clear()
    else:
        attempted = entries
    fe.blocked = False
    fe.loop(deque(), seconds + DRAIN_CAP_S, admit_new=False, cfg=None,
            wait=False)
    give_up = fe.now()
    for e in attempted:
        if not e.rec.done:
            e.rec.failed = True
    failed = sum(e.rec.failed for e in attempted)
    late = [e.rec.queued - e.rec.due for e in attempted
            if e.rec.queued is not None]
    log(f"window: {len(attempted)} requests attempted, {failed} failed, "
        f"{tick1 - tick0} ticks, {len(fe.admit_calls)} admit calls; drain "
        f"ended {give_up - seconds:.3f}s after the close")
    log(f"compilations inside the window: {in_window_compiles}; compile "
        f"path inside the window: " + (", ".join(
            f"{k} x{n} {1e3 * t:.3f} ms" for k, (n, t) in
            sorted(jit_events.items())) or "nothing"))
    log(f"longest call inside the window: admit "
        f"{1e3 * fe.longest['admit']:.3f} ms, tick "
        f"{1e3 * fe.longest['tick']:.3f} ms; garbage collection: "
        f"{len(gc_pauses)} passes, longest "
        f"{1e3 * max(gc_pauses, default=0):.3f} ms, "
        f"{1e3 * sum(gc_pauses):.3f} ms in all")
    if late:
        log(f"generator lateness: p95 {1e3 * e2e.percentile(late, 95):.3f} "
            f"ms, max {1e3 * max(late):.3f} ms")
    records = [e.rec for e in attempted]
    memory_peak = _peak_bytes(devices)
    log(f"device memory after the drain: {device.memory_stats()}")

    result = {"correct": False, "attempted": len(attempted),
              "failed": failed, "metrics": {}}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = tracing.reduce(tracing.read_xspace(files[0]), MODULES)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        peak = roofline.peaks(device.device_kind) if require_tpu else None
        ctx = LayerCtx(cfg=cfg, records=records, window_s=seconds,
                       give_up=give_up, admit_calls=fe.admit_calls,
                       prefill_lens=fe.prefill_lens, tick_work=fe.tick_work,
                       tick_stats=[r for r in engine.tick_stats
                                   if tick0 <= r["tick"] < tick1],
                       shared_hits=fe.shared_hits,
                       prompt_pages=fe.prompt_pages, trace=reduced,
                       peak=peak)
        for m in spec.per_layer_for(name, bench):
            value = spec.load_metric(m["name"], root).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    else:
        if require_tpu:
            roofline.peaks(device.device_kind)
        values = e2e.summarize(records, seconds, give_up,
                               [m["name"] for m in
                                spec.end_to_end_for(name, bench)])
        values["setup_s"] = setup_s
        for m in spec.end_to_end_for(name, bench):
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}
    result["device"] = dev
    result["notes"] = {"compiles_in_window": in_window_compiles,
                       "compile_path_ms_in_window": 1e3 * sum(
                           t for _, t in jit_events.values()),
                       "queue_at_close": queue_at_close,
                       "ticks": tick1 - tick0,
                       "longest_admit_ms": 1e3 * fe.longest["admit"],
                       "longest_tick_ms": 1e3 * fe.longest["tick"],
                       "gc_ms": 1e3 * sum(gc_pauses),
                       "lateness_p95_ms": 1e3 * e2e.percentile(late, 95)
                       if late else None}

    # the check, once the program's state is freed
    engine.cache = None
    del engine, fe
    gc.collect()
    t = time.perf_counter()
    checks = correctness(cfg, params, attempted, seed, root, control)
    log(f"reference check: {time.perf_counter() - t:.3f}s")
    result["correct"] = all(c["value"] <= c["limit"]
                            for k, c in checks.items()
                            if k != "control_logit_gap")
    result["checks"] = checks
    return result


def correctness(cfg: dict, params, attempted: List[Entry], seed: int,
                root: str = spec.ROOT, control: bool = False) -> dict:
    """The numbers compared, each with its limit (``control``: with the
    float8 control's gap over the same sample, held to the same limit)."""
    finished = [e for e in attempted if e.rec.done and not e.rec.failed]
    rng = np.random.default_rng([int(seed), 0x636865636B])
    picked = check.sample(finished, rng)
    reference = spec.load_reference(cfg["reference"], root)
    # with nothing finished to compare, the gap reads as far off
    worst = low = 0.0 if picked else NOTHING_COMPARED
    served = 0
    for e in picked:
        g = check.gaps(reference, params, cfg, e.req.prompt, e.generated)
        worst = max(worst, float(g.max()))
        served += len(g)
        if control:
            low = max(low, float(check.gaps(reference, params, cfg,
                                            e.req.prompt, e.generated,
                                            control=True).max()))
    log(f"reference check: {len(picked)} requests, {served} served tokens")
    short = sum(len(e.generated) != e.rec.max_new for e in finished)
    limit = cfg["limits"]["logit_gap"]
    out = {"logit_gap": {"value": worst, "limit": limit},
           "short_requests": {"value": short, "limit": 0}}
    if control:
        out["control_logit_gap"] = {"value": low, "limit": limit}
    return out
