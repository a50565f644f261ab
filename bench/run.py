"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: a model configuration under
a traffic mix. The run builds the program's ``PhaseEngine`` and drives
``PhaseEngine.run``, the production training loop, in two calls that share one
engine, one state and one token feed:

1. set-up: the weights are made on the device from the seed, and the
   first phase runs (it compiles the phase program the window runs).
   Its losses, dispersions and final state are kept for the check.
2. the window: ``run`` continues from that state on the same feed. The
   program emits one ``phase_metrics`` record per phase; the window
   opens when the second phase of this call is recorded and closes when
   the first phase that ends after ``--seconds`` is recorded. It holds
   the gaps between phases (staging, dispatch, fetch); it leaves out
   set-up, compilation and ``finish()``'s consensus.

With ``--trace 1`` the profiler records the first phases of the window
and the per-layer metrics are read from that trace; otherwise the
end-to-end metrics are reported. After the window the peak device
memory is read, the program's state is let go, and the plain reference
reruns the first phase; ``correct`` is the comparison of the two
(``bench/check.py``). The last line of standard output is one JSON
object. A host without a TPU, or with fewer chips than the cell asks
for, gets a message and a non-zero exit, and no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")
TRACE_PHASES, TRACE_SECONDS = 2, 2.0
# phases of the window call before the window opens: the first runs on
# the state as set-up hands it over, the second on the state run_phase
# returned, whose sharding on a mesh is another signature of the phase
# program (traced and loaded on its first call)
WARM_PHASES = 2


class NoChip(RuntimeError):
    pass


def find_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def enable_cache(path: str = CACHE_DIR):
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a size cap would evict the phase program whenever a larger one is
    # written after it, and every run would compile it again
    jax.config.update("jax_compilation_cache_max_size", -1)


class WindowSink:
    """Receives the program's telemetry records during the window call
    (``PhaseEngine.run(sink=...)``) and keeps the window's clock from
    its ``phase_metrics`` records."""

    def __init__(self, seconds: float, tracer=None):
        self.seconds = seconds
        self.tracer = tracer
        self.phases = []          # (time, steps, loss_mean, losses)
        self.t_start = self.deadline = self.t_end = None
        self.steps = 0

    def emit(self, rec):
        if rec["type"] != "phase_metrics":
            return
        now = time.perf_counter()
        losses = [v for _, v in rec.get("loss_trace", [])]
        self.phases.append((now, rec["steps"], rec["loss_mean"], losses))
        if len(self.phases) < WARM_PHASES:
            return
        if self.t_start is None:
            self.t_start, self.deadline = now, now + self.seconds
        elif self.t_end is None:
            self.steps += rec["steps"]
            if now >= self.deadline:
                self.t_end = now
        if self.tracer is not None:
            self.tracer.phase(now, rec["steps"])

    def close(self):
        pass

    def feeding(self) -> bool:
        """False once the feed should stop: the window is over and so is
        the trace."""
        over = self.deadline is not None and time.perf_counter() >= self.deadline
        traced = self.tracer is None or self.tracer.done
        return not (over and traced)

    def window_phases(self):
        """Phases recorded inside the window."""
        return [p for p in self.phases[WARM_PHASES:] if p[0] <= self.t_end]


class Tracer:
    """Starts the profiler when the window opens and stops it after
    ``TRACE_PHASES`` phases and ``TRACE_SECONDS``; a host span
    ``bench.window`` marks the traced stretch on the trace's clock."""

    def __init__(self, path: str):
        self.path = path
        self.done = False
        self.t0 = None
        self.n = 0
        self.steps = 0
        self._span = None

    def phase(self, now, steps):
        import jax
        if self.done:
            return
        if self.t0 is None:
            shutil.rmtree(self.path, ignore_errors=True)
            jax.profiler.start_trace(self.path)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.t0 = now
            return
        self.n += 1
        self.steps += steps
        if self.n >= TRACE_PHASES and now - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        import jax
        if self._span is not None and not self.done:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.done = True


class CompileLog:
    """What JAX reports about building programs in this process: when
    each one was traced, compiled or loaded from the persistent cache,
    and totals for the set-up line of the log."""

    BUILD = ("jaxpr_trace_duration", "backend_compile_duration",
             "cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.times = []
        self.secs = {}
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)

    def _on(self, event, duration, **kw):
        key = event.rsplit("/", 1)[-1]
        if key in self.BUILD:
            self.times.append(time.perf_counter())
        self.secs[key] = self.secs.get(key, 0.0) + duration

    def _count(self, event, **kw):
        key = event.rsplit("/", 1)[-1]
        self.counts[key] = self.counts.get(key, 0) + 1

    def within(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t in self.times)

    def summary(self) -> str:
        secs = ", ".join(f"{k} {v:.2f} s" for k, v in sorted(self.secs.items()))
        return f"{secs}; {dict(sorted(self.counts.items()))}"


def reduce_trace(path: str, devices: int, trace_steps: int):
    """The traced stretch (the ``bench.window`` span) of the cell's
    devices: per device its leaf ops, busy seconds, and the breakdown."""
    from bench import trace as tr
    t = tr.load(path)
    win = tr.span(t, "bench.window")
    if win is None:
        raise RuntimeError("the trace holds no bench.window span")
    t0, t1 = win
    devs = sorted(t["devices"])[:devices]
    if not devs:
        raise RuntimeError("the trace holds no TPU op line")
    leaves = {d: tr.leaves(t["devices"][d]) for d in devs}
    ops = {}
    for d in devs:
        for lab, s in tr.top_ops(t["devices"][d], t0, t1, n=40):
            ops[lab] = ops.get(lab, 0.0) + s / len(devs)
    return SimpleNamespace(
        events=leaves, t0=t0, t1=t1, window_s=(t1 - t0) / 1e9,
        steps=trace_steps,
        busy_s=[tr.busy_ns(leaves[d], t0, t1) / 1e9 for d in devs],
        device_ops=sorted(([k, v] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:10],
        idle_gaps=tr.idle_gaps(leaves[devs[0]], t["host"], t0, t1))


def build(name: str, root=ROOT):
    """The cell's pieces and the program's engine (on a mesh over the
    visible chips where the cell asks for more than one)."""
    from bench import spec
    c = spec.cell(name, root)
    cfg, tr, wl = c["config"], c["traffic"], c["workload"]
    adapter, ref = spec.model(cfg["model"])
    mesh = None
    if wl["chips"] > 1:
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(tr["workers"])
    engine = adapter.make_engine(cfg, tr, mesh=mesh)
    return SimpleNamespace(c=c, cfg=cfg, tr=tr, wl=wl, adapter=adapter,
                           ref=ref, engine=engine)


def first_phase(cell, seed: int, feed):
    """Set-up: the weights from the seed, then the first phase through
    ``PhaseEngine.run`` on ``feed`` (it compiles the phase program the
    window runs). Returns the program's side of the check and the
    state, in tree form, that the window continues from."""
    cfg, tr = cell.cfg, cell.tr
    params = cell.adapter.make_params(cfg, seed)
    _, hist, state = cell.engine.run(
        params, feed, num_workers=tr["workers"], seed=seed % (2 ** 31),
        phase_len=tr["phase_len"], steps=tr["phase_len"], record_every=1,
        return_state=True)
    del params
    prog = {"loss": [v for _, v in hist["loss"]],
            "dispersion": [v for _, v in hist["disp_trace"]]}
    prog["change"], prog["velocity"] = cell.adapter.state_norms(
        cfg, state, seed)
    return prog, state


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             *, root=ROOT, log=print) -> dict:
    """One run of cell ``name``; returns the result dict (``correct``,
    ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
    ``breakdown``; ``checks`` last)."""
    import jax
    import numpy as np
    from bench import check, spec
    from bench.feed import Feed, TokenBlocks

    compiles = CompileLog()
    cell = build(name, root)
    c, cfg, tr, wl, ref = cell.c, cell.cfg, cell.tr, cell.wl, cell.ref
    m, k = tr["workers"], tr["phase_len"]
    used = devices[:wl["chips"]]
    trace_dir = os.path.join(root, "bench", ".trace", name)
    sink = WindowSink(seconds, Tracer(trace_dir) if trace else None)
    blocks = TokenBlocks(tr, ref.shape(cfg)["vocab"], seed)
    feed = Feed(blocks, stop=lambda: not sink.feeding(),
                annotate=jax.profiler.TraceAnnotation)
    t_first = time.perf_counter()
    prog, state = first_phase(cell, seed, feed)
    box = [state]
    del state
    t_window_call = time.perf_counter()

    # the window: the same engine, state and feed
    try:
        with jax.profiler.TraceAnnotation("bench.run"):
            out = cell.engine.run(None, feed, num_workers=m,
                                  seed=seed % (2 ** 31),
                                  phase_len=k, record_every=1,
                                  state=box.pop(), sink=sink)
    finally:
        if sink.tracer is not None:
            sink.tracer.stop()
    t_ret = time.perf_counter()
    del out
    if sink.t_end is None:
        raise RuntimeError("the window never closed: the feed ended early")
    window_s = sink.t_end - sink.t_start
    setup_s = sink.t_start - T_PROCESS
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    peak = max(peaks)
    phases = sink.window_phases()
    losses = [v for p in phases for v in p[3]]
    failed = sum(not math.isfinite(v) for v in losses)
    log(f"[bench] {name}: {cfg['name']} x {wl['traffic']}, {m} workers, "
        f"batch {tr['batch']} x seq {tr['seq']}, K={k}, "
        f"{len(used)} x {used[0].device_kind}")
    log(f"[bench] set-up {setup_s!r} s; window {window_s!r} s, "
        f"{sink.steps} steps in {len(phases)} phases")
    log(f"[bench] mean loss of each window phase: {[p[2] for p in phases]!r}")
    ends = [sink.t_start] + [p[0] for p in phases]
    log(f"[bench] seconds of each window phase: "
        f"{[b - a for a, b in zip(ends, ends[1:])]!r}")
    log(f"[bench] token generator: {blocks.host_s / blocks.steps * 1e3!r} "
        f"ms of host time per step")
    log(f"[bench] after the last phase until run() returned (finish(): "
        f"consensus of every leaf): {t_ret - sink.phases[-1][0]!r} s")
    log(f"[bench] set-up stages: to the first phase {t_first - T_PROCESS!r}"
        f" s, first phase and its readings {t_window_call - t_first!r} s, "
        f"window call to the window's start "
        f"{sink.t_start - t_window_call!r} s")
    log(f"[bench] compiling: {compiles.summary()}")
    log(f"[bench] programs traced, compiled or loaded inside the window: "
        f"{compiles.within(sink.t_start, sink.t_end)}")
    log(f"[bench] peak_bytes_in_use per device: {peaks}")

    traced = None
    if trace:
        traced = reduce_trace(trace_dir, len(used), sink.tracer.steps)
    ctx = SimpleNamespace(
        workload=name, config=cfg, traffic=tr, chips=len(used),
        shape=ref.shape(cfg), width=ref.width(cfg),
        peak=spec.peaks(used[0].device_kind, root) if trace else None,
        tokens_per_step=m * tr["batch"] * tr["seq"],
        window_steps=sink.steps, window_s=window_s, setup_s=setup_s,
        memory_peak_bytes=peak, trace=traced)

    # the reference, once the program's state is gone
    t_ref = time.perf_counter()
    ref_out = ref.train_phase(cfg, tr, seed, feed.first_block, devices=used)
    log(f"[bench] reference first phase: {time.perf_counter() - t_ref!r} s;"
        f" compiling since set-up: {compiles.summary()}")
    read = check.readings(prog, ref_out)
    log(f"[bench] program loss {prog['loss']!r}")
    log(f"[bench] reference loss {ref_out['loss']!r}")
    log(f"[bench] program dispersion {prog['dispersion']!r}")
    log(f"[bench] reference dispersion {ref_out['dispersion']!r}")
    log(f"[bench] worst leaves: change {read['worst_change_leaf']}, "
        f"velocity {read['worst_velocity_leaf']}; left out of the change "
        f"(no gradient): {read['idle_leaves']}")
    ok, checks = check.decide(read, c["limits"])

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for mtr in c[kind]:
        v = spec.reader(mtr["name"], root)(ctx)
        if v is not None:
            metrics[mtr["name"]] = {"value": float(v), "unit": mtr["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(ok and failed == 0 and sink.steps > 0),
              "attempted": sink.steps, "failed": failed,
              "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = float(np.mean(traced.busy_s))
        device["window_s"] = traced.window_s
        result["breakdown"] = {"device_ops": traced.device_ops,
                               "idle_gaps": traced.idle_gaps}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from bench import spec
        chips = spec.cell(args.workload)["workload"]["chips"]
        devices = find_devices(chips)
        enable_cache()
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), devices)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for n, c in result["checks"].items():
        print(f"{n} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
