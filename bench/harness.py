"""One benchmark run of one cell: set-up, load, measured window, metrics.

Everything that belongs to a cell is found by name: the configuration
(``bench/configs/<config>.json``), the traffic mix
(``bench/traffic/<traffic>.json``), the cell's serving shape and
correctness limits (``bench/cells/<cell>.json``), and each per-layer
metric (``bench/metrics/<metric>.py``).

The window drives the system's own front end: ``Frontend.submit`` when a
request falls due and ``Frontend.tick`` on the real clock, over one
``Engine``. Tokens count as delivered when a ticket holds them after a
tick.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
clock = time.perf_counter


# ------------------------------------------------------------------ specs

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the BENCHMARK.json workload entry
    config: dict
    traffic: dict
    shape: dict          # bench/cells/<name>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names or "moves" not in metric


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"] if reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(name, entry, load_json(ROOT / conf["file"]),
                load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                load_json(BENCH / "cells" / f"{name}.json"), e2e, per_layer)


def dims(config: dict) -> dict:
    """The sizes the cost functions take, by their short names, and how
    the block's linears run: int8 through the macro, or bf16."""
    sim = config["serving"]["cim_mode"] == "sim"
    return {"linear": "int8" if sim else "bf16",
            "n_layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"]}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import CIMModelConfig, ModelConfig

    s = config["serving"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        qkv_bias=config["attention_bias"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"],
        attn_impl=s["attn_impl"],
        kv_cache_int8=s["kv_cache_dtype"] == "int8",
        cim=CIMModelConfig(mode=s["cim_mode"], policy=s["cim_policy"],
                           act_clip_sigmas=s["act_clip_sigmas"],
                           use_kernel=s["cim_use_kernel"]))


def seed32(seed: int) -> int:
    """A 31-bit seed for JAX keys, derived from any whole number."""
    return int(np.random.SeedSequence(int(seed) % (1 << 63))
               .generate_state(1)[0] & 0x7FFFFFFF)


# ------------------------------------------------------------------ load

@dataclasses.dataclass
class Rec:
    item: object
    due: float
    submitted: float
    ticket: object
    n: int = 0
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n)
    first: Optional[float] = None
    done: Optional[float] = None
    outcome: Optional[str] = None


@dataclasses.dataclass
class Tick:
    """What one scheduler iteration did, from the engine's slot state."""
    decode_lens: list       # context length of each decoded row
    chunks: list            # (start, valid, final) of each prefill chunk


def _slots(eng) -> list:
    return [None if r is None else (id(r), off, dec, cnt, len(r.prompt))
            for r, off, dec, cnt in zip(eng._slots, eng._offsets,
                                        eng._decoding, eng._counts)]


def tick_work(pre: list, post: list) -> Tick:
    """Diff the slot state around one tick: a slot that kept its request
    decoded a token or advanced its prefill; a request that left its slot
    while decoding decoded its last token; a new occupant with a written
    offset had its first chunk."""
    lens, chunks = [], []
    for a, b in zip(pre, post):
        same = a is not None and b is not None and a[0] == b[0]
        if a is not None and not same and a[2]:
            lens.append(a[4] + a[3])            # decoded its last token
        if b is None:
            continue
        start = a[1] if same else 0
        if same and a[2]:
            if b[3] > a[3]:
                lens.append(b[4] + a[3])
        elif b[1] > start:
            chunks.append((start, b[1] - start, b[2]))
            if b[2] and b[3] >= 2:
                lens.append(b[4] + 1)           # joined this step's decode
    return Tick(lens, chunks)


class Driver:
    """Closed- or open-loop load over one ``Frontend``."""

    def __init__(self, fe, pool, traffic: dict, clients: int):
        self.fe = fe
        self.eng = fe.engine
        self.pool = pool
        self.closed = traffic["loop"] == "closed"
        self.think = float(traffic.get("think_s", 0.0))
        self.temp = float(traffic["temperature"])
        self.clients = clients
        self.recs: List[Rec] = []
        self.live: List[Rec] = []
        self.ready: List[float] = []     # closed loop: due times of clients
        self.next = 0
        self.start: Optional[float] = None
        self.completions = 0
        self.spans = None                # None, or name -> context manager
        self.ticks: Optional[List[Tick]] = None   # counting when a list
        self.closed_at: Optional[float] = None    # no submissions after

    def _span(self, name: str):
        return self.spans(name) if self.spans else contextlib.nullcontext()

    def begin(self, backlog: int = 0) -> None:
        """Start the loop: closed, every client due now; open, the next
        ``backlog + 1`` requests of the pool due now and the rest on their
        schedule. A backlog at the start makes an open loop above the knee
        start as it goes on: every slot that frees takes the next request
        at once, so which requests share the slots does not hang on how
        the first arrivals met the first iterations."""
        self.start = clock()
        if self.closed:
            self.ready = [self.start] * self.clients
        elif self.next < len(self.pool.items):
            i = min(self.next + backlog, len(self.pool.items) - 1)
            self.start -= self.pool.items[i].due

    def prime(self, n: int, limit_s: float,
              max_new: Optional[int] = None) -> None:
        """Serve the pool's first ``n`` requests, all submitted at once,
        to their end, each cut to ``max_new`` tokens where given: every
        program the loop uses (chunked prefill, prefill mixed with decode,
        decode alone) is built before the loop's clock starts."""
        self.start = clock()
        for _ in range(n):
            self._submit(clock(), max_new)
        self.closed_at = self.start
        try:
            self.run_until(n, limit_s)
        finally:
            self.closed_at = None

    def _submit(self, due: float, max_new: Optional[int] = None) -> None:
        item = self.pool.items[self.next]
        self.next += 1
        prompt = self.pool.prompt(item)
        with self._span("submit"):
            t = self.fe.submit(prompt, max_new=min(item.max_new,
                                                   max_new or item.max_new),
                               temperature=self.temp, rid=f"r{item.index}")
        rec = Rec(item, due, clock(), t)
        self.recs.append(rec)
        self.live.append(rec)

    def step(self) -> bool:
        now = clock()
        with self._span("loadgen"):
            if self.closed:
                due = [d for d in self.ready if d <= now]
                self.ready = [d for d in self.ready if d > now]
            else:
                due = []
                while (self.next + len(due) < len(self.pool.items)
                       and self.start + self.pool.items[
                           self.next + len(due)].due <= now):
                    due.append(self.start
                               + self.pool.items[self.next + len(due)].due)
        if self.closed_at is not None:
            due = []
        for d in due:
            if self.next >= len(self.pool.items):
                raise RuntimeError("the request pool ran out; raise 'pool'")
            self._submit(d)
        busy = self.fe.pending() > 0
        pre = _slots(self.eng) if self.ticks is not None else None
        with self._span("tick" if busy else "idle_tick"):
            self.fe.tick()
        t = clock()
        if pre is not None:
            self.ticks.append(tick_work(pre, _slots(self.eng)))
        for rec in list(self.live):
            tk = rec.ticket
            if tk.request is None and not tk.done.is_set():
                # admission is first in, first out: this request and every
                # later one still wait in the backlog, so the per-tick cost
                # does not grow with the backlog above the knee
                break
            n = len(tk.tokens)
            if n > rec.n:
                rec.deliveries.append((t, n - rec.n))
                if rec.first is None:
                    rec.first = t
                rec.n = n
            if tk.done.is_set():
                rec.done, rec.outcome = t, tk.outcome
                self.live.remove(rec)
                self.completions += 1
                if self.closed:
                    self.ready.append(t + self.think)
        if not busy and not due:
            nxt = self._next_due()
            if nxt is not None and nxt > t:
                time.sleep(min(nxt - t, 0.001))
        return busy

    def _next_due(self) -> Optional[float]:
        if self.closed:
            return min(self.ready) if self.ready else None
        if self.next < len(self.pool.items):
            return self.start + self.pool.items[self.next].due
        return None

    def kv_fill(self) -> float:
        """Share of the slot cache's positions that hold a live key."""
        e = self.eng
        live = sum(int(off) + int(cnt) for r, off, cnt in
                   zip(e._slots, e._offsets, e._counts) if r is not None)
        return live / (e.max_slots * e.max_len)

    def run_until(self, completions: int, limit_s: float) -> None:
        t_stop = clock() + limit_s
        while self.completions < completions:
            self.step()
            if clock() > t_stop:
                raise RuntimeError(
                    f"warm-up did not reach {completions} completions in "
                    f"{limit_s:.0f} s ({self.completions} done)")


# --------------------------------------------------------------- metrics

def percentile(xs: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]): an observed value."""
    if not xs:
        return None
    s = sorted(xs)
    rank = max(0, min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1)))))
    return s[rank]


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    recs: List[Rec]

    def due(self) -> List[Rec]:
        return [r for r in self.recs if self.t0 <= r.due < self.t1]

    def out_tokens(self) -> int:
        return sum(n for r in self.recs for t, n in r.deliveries
                   if self.t0 <= t < self.t1)

    def gaps(self) -> List[float]:
        out = []
        for r in self.recs:
            prev = None
            for t, n in r.deliveries:
                if prev is not None and self.t0 <= t < self.t1:
                    out.append(t - prev)
                    out.extend([0.0] * (n - 1))
                elif prev is None and self.t0 <= t < self.t1:
                    out.extend([0.0] * (n - 1))
                prev = t
        return out

    def ttfts(self) -> List[float]:
        out = []
        for r in self.due():
            if r.first is not None and r.first < self.t1:
                out.append(r.first - r.due)
            else:
                out.append(self.t1 - r.due)
        return out

    def failed(self) -> int:
        return sum(r.outcome not in (None, "completed") for r in self.due())


def end_to_end(name: str, win: Window, setup_s: float) -> float:
    """An end-to-end metric by name: ``setup_s``, ``out_tok_s``,
    ``itl_p<q>_ms``, ``ttft_p<q>_ms``."""
    if name == "setup_s":
        return setup_s
    if name == "out_tok_s":
        return win.out_tokens() / (win.t1 - win.t0)
    kind, q, unit = name.split("_")
    q = float(q[1:])
    xs = win.gaps() if kind == "itl" else win.ttfts()
    v = percentile(xs, q)
    if v is None:
        raise RuntimeError(f"no samples for {name}")
    return v * 1e3


def late_ms(win: Window) -> Optional[float]:
    """How late the generator submitted the window's requests (p95, ms)."""
    return percentile([(r.submitted - r.due) * 1e3 for r in win.due()], 95)


# ------------------------------------------------------------------- run

@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    memory_peak_bytes: Optional[int]
    checks: Dict[str, dict]
    correct: bool
    busy_s: Optional[float] = None
    window_s: Optional[float] = None
    breakdown: Optional[dict] = None
    notes: Optional[dict] = None
    path: Optional[dict] = None      # the engine path the window timed


def run(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, readings: bool = False) -> Result:
    """Set up, warm, measure, then check against the plain reference."""
    import jax

    from bench import check, loadgen, trace, weights
    from repro.serving.engine import Engine
    from repro.serving.frontend import Frontend

    shape, traffic, config = cell.shape, cell.traffic, cell.config
    s32 = seed32(seed)
    mcfg = model_config(config)
    params = weights.make(config, s32)
    jax.block_until_ready(params)
    serving = config["serving"]
    engine = Engine(mcfg, params, max_slots=shape["max_slots"],
                    max_len=shape["max_len"], cim_mode=serving["cim_mode"],
                    seed=s32, chunk_size=shape["chunk_size"],
                    deploy=serving["deployed_planes"])
    del params
    closed = traffic["loop"] == "closed"
    clients = (int(traffic["clients_per_slot"] * shape["max_slots"])
               if closed else 0)
    pool = loadgen.make_pool(traffic, seed, config["vocab_size"],
                             int(traffic["pool"]),
                             rate=float(shape.get("rate_rps") or 0.0))
    fused = watch_fused_step(engine)
    fe = Frontend(engine, queue_limit=max(clients, len(pool.items)) + 1,
                  max_retries=0, clock=clock)
    drv = Driver(fe, pool, traffic, clients)
    limit_s = float(shape.get("warm_limit_s", 900))
    if not closed and traffic.get("prime_requests"):
        drv.prime(int(traffic["prime_requests"]), limit_s,
                  traffic.get("prime_max_new"))
    warm = (int(round(traffic["warm_completions_per_client"] * clients))
            if closed else int(traffic["warm_completions"]))
    drv.begin(int(traffic.get("start_backlog", 0)))
    drv.run_until(drv.completions + warm, limit_s=limit_s)
    setup_s = clock() - t_start

    summary = None
    tw = None
    if trace_dir:
        drv.spans = jax.profiler.TraceAnnotation
        drv.ticks = []
        jax.profiler.start_trace(trace_dir)
    t0 = clock()
    if trace_dir:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            while clock() < t0 + shape["trace_seconds"]:
                drv.step()
        tw = (t0, clock())
        jax.profiler.stop_trace()
        drv.spans = None
        ticks, drv.ticks = drv.ticks, None
    fill, n = [], 0
    while clock() < t0 + seconds:
        drv.step()
        n += 1
        if n % 16 == 0:
            fill.append(drv.kv_fill())
    win = Window(t0, clock(), list(drv.recs))
    # the check compares requests the window finished; where none did (long
    # outputs at a slow step), serve on without new submissions until one
    # that was in flight in the window finishes
    drv.closed_at = win.t1
    stop = win.t1 + float(shape.get("check_wait_s", 120))
    while not check.finished(win) and drv.live and clock() < stop:
        drv.step()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    mem = stats.get("peak_bytes_in_use")
    metrics = {}
    if not trace_dir:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end(m["name"], win, setup_s),
                                  "unit": m["unit"]}
    busy_s = window_s = breakdown = None
    if trace_dir:
        summary = trace.load(trace_dir)
        from bench import flops, peaks
        reading = Reading(summary=summary, ticks=ticks,
                          recs=[r for r in win.recs
                                if tw[0] <= r.submitted < tw[1]],
                          dims=dims(config), shape=shape,
                          peaks=peaks.peaks_for(dev.device_kind),
                          flops=flops)
        for m in cell.per_layer:
            mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                              m["name"])
            v = mod.read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy_s, window_s = summary.busy_s, summary.window_s
        breakdown = summary.breakdown()

    sample = check.pick(win, seed, cell.shape)
    served = [(drv.pool.prompt(r.item), np.asarray(r.ticket.tokens))
              for r in sample]
    attempted, failed = len(win.due()), win.failed()
    half = (win.t0 + win.t1) / 2
    notes = {"requests_due": attempted, "sampled": len(served),
             "completed_rps": sum(win.t0 <= r.done < win.t1 for r in win.recs
                                  if r.done is not None) / (win.t1 - win.t0),
             "sampled_tokens": int(sum(len(s) for _, s in served)),
             "loadgen_late_p95_ms": late_ms(win),
             "pending_at_close": fe.pending(),
             "kv_fill_mean": float(np.mean(fill)) if fill else None,
             "ttft_p50_ms_halves": [
                 1e3 * (percentile(Window(a, b, win.recs).ttfts(), 50) or 0)
                 for a, b in ((win.t0, half), (half, win.t1))]}
    # free the program's state before the reference runs
    engine_fused_ok = engine._fused_ok
    del drv, fe, engine, win, summary
    gc.collect()
    checks, correct, extra = check.run(cell, s32, served,
                                       readings_too=readings)
    notes.update(extra)
    path = {"fused_step": bool(engine_fused_ok), "fused_step_error":
            fused["error"]}
    return Result(attempted, failed, metrics, mem, checks,
                  correct and attempted > 0,
                  busy_s, window_s, breakdown, notes, path)


def watch_fused_step(engine) -> dict:
    """Record why the engine's fused iteration program fails, where it
    does: the engine catches the error and serves on through its per-call
    programs, so without this a run could not say which path it timed."""
    seen = {"error": None}
    step = engine._step

    def watched(*args, **kw):
        try:
            return step(*args, **kw)
        except Exception as e:                   # noqa: BLE001
            if seen["error"] is None:
                import traceback

                seen["error"] = "".join(traceback.format_exception_only(
                    type(e), e))[-1500:]
            raise

    engine._step = watched
    return seen


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reads: the reduced trace, the scheduler's
    work per traced tick, the requests submitted while tracing, and the
    tables to compare with."""
    summary: object
    ticks: List[Tick]
    recs: List[Rec]
    dims: dict
    shape: dict
    peaks: dict
    flops: object
