"""Chip smoke test: the local-SGD engine's main path on a TPU.

Run from the repository root, in one process that owns the chip(s):

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded worker axis, 4 chips

One chip:
  1. ``repro.launch.train.main`` trains smollm-360m at its full published
     width (32 layers, d_model 960, bf16 params, f32 planes) with 2
     workers for 8 steps, periodic-4 averaging: finite loss, at least 2
     averaging events, a finite final dispersion.
  2. The same full-width bf16 phase, compiled: on one chip the engine
     carries the leaves (``PhaseEngine.carry``), so the program holds
     no (M, P) plane and no Mosaic update kernel.
  3. The dtype rounding (bf16, and f16 in f32 arithmetic) compiled by
     Mosaic in a kernel and by XLA in the jnp twin, against numpy's
     casts, bit for bit.
  4. smollm-360m ``--reduced`` with 4 workers, periodic-2, at its own
     bf16 params and in f32 (model matmuls at full f32 precision). On
     the plane carry, asked for by its layout, the Pallas kernels
     (Mosaic ``opt_step`` and event) against their jnp twins: losses
     and dispersions agree at f32 roundoff. The leaf carry the engine
     takes against that plane carry: at f32 to roundoff; at bf16 within
     the benchmark's limits for the one-chip cell
     (``bench/limits/smollm360m-m2-s128-k4.json``), since XLA may skip
     a bf16 rounding inside the leaf carry's fusions as excess
     precision where the kernel rounds, and one flipped rounding moves
     the loss by more than f32 roundoff.

Four chips (``--chips 4``), and nothing else:
  1. ``train.main`` at full width with 8 workers sharded 2 rows per chip
     under the ``psum`` collective; the state is created sharded and
     every chip holds exactly its 2 rows.
  2. ``--reduced`` with 8 workers over the 4 chips under ``psum``
     against the same run unsharded on one chip.

Weights come from ``init_params`` and tokens from ``token_stream``, both
seeded. Any failure raises (non-zero exit). The last line of standard
output is one JSON object naming the device, printed only on success.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL_ARGS = ["--arch", "smollm-360m", "--steps", "8", "--avg", "periodic",
             "--phase-len", "4", "--batch", "4", "--seq", "128",
             "--seed", "0"]


def _peaks(jax):
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def _train_full_width(jax, workers: int, extra=()):
    """train.main at full width; returns (history, final state)."""
    from repro.launch.train import main as train_main
    with tempfile.TemporaryDirectory() as tmp:
        tele = os.path.join(tmp, "train.jsonl")
        argv = FULL_ARGS + ["--workers", str(workers), "--telemetry", tele,
                            *extra]
        print(f"[smoke] train.main {' '.join(argv)}", flush=True)
        t0 = time.perf_counter()
        _, hist, state = train_main(argv)
        wall = time.perf_counter() - t0
        with open(tele) as f:
            phases = [r for r in map(json.loads, f)
                      if r.get("type") == "phase_metrics"]
    losses = [v for _, v in hist["loss"]]
    assert losses and all(math.isfinite(v) for v in losses), hist["loss"]
    assert hist["averages"] >= 2, hist["averages"]
    assert math.isfinite(hist["dispersion"][-1][1]), hist["dispersion"]
    warm = phases[-1]  # every phase but the first runs compiled code
    print(f"[smoke] full width: {hist['averages']} averaging events, "
          f"loss {losses[-1]!r}, final dispersion "
          f"{hist['dispersion'][-1][1]!r}, {wall:.1f} s including "
          "compilation", flush=True)
    print(f"[smoke] per-step time after warm-up: "
          f"{warm['wall_s'] / (warm['t1'] - warm['t0'] + 1) * 1e3!r} ms "
          f"(steps {warm['t0']}-{warm['t1']}; smoke reading, not a "
          "benchmark)", flush=True)
    for d, peak in zip(jax.devices(), _peaks(jax)):
        print(f"[smoke] {d} peak_bytes_in_use {peak}", flush=True)
    return hist, state


def _engine(cfg, kernel_impl: str, mesh=None):
    from repro.core import AveragingSchedule, PhaseEngine
    from repro.models import lm_loss
    from repro.optim import Momentum
    return PhaseEngine(lambda p, b, r: lm_loss(cfg, p, b),
                       Momentum(lr=0.05, mu=0.9),
                       AveragingSchedule("periodic", 2),
                       kernel_impl=kernel_impl, mesh=mesh)


def _batches(cfg, workers: int, steps: int, seq: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.data import token_stream
    streams = [token_stream(cfg.vocab_size, 4, seq, seed=131 + i)
               for i in range(workers)]
    return [{"tokens": jnp.asarray(np.stack([next(s) for s in streams]))}
            for _ in range(steps)]


def _reduced(jax, dtype: str):
    """smollm-360m --reduced at ``dtype``: (config, seeded params)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_params
    cfg = dataclasses.replace(get_config("smollm-360m", reduced=True),
                              dtype=dtype)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _reduced_run(jax, workers: int, kernel_impl: str, mesh=None,
                 dtype: str = "float32"):
    """Six steps of smollm-360m --reduced at ``dtype`` through
    PhaseEngine.run with per-step records; returns the history.

    The model's matmuls run at full f32 precision: at the TPU's default
    precision their f32 operands are rounded to bf16, so a last-ulp
    difference between two paths (a psum-ordered average against an
    in-kernel mean) can flip a bf16 rounding and show up as a 1e-5
    loss difference; at f32 the paths agree at f32 roundoff."""
    cfg, params = _reduced(jax, dtype)
    engine = _engine(cfg, kernel_impl, mesh)
    with jax.default_matmul_precision("highest"):
        _, hist = engine.run(params, _batches(cfg, workers, 6, 64),
                             num_workers=workers, record_every=1,
                             phase_len=2)
    return hist


def _full_width_phase(jax):
    """Compile one phase of full-width smollm-360m (bf16 params, 2
    workers, batch 4 x seq 128, an averaging event) on abstract state
    and check that it carries the leaves: no plane, no Mosaic update."""
    from repro.configs import get_config
    from repro.core.engine import tree_stack
    from repro.models import init_params
    cfg = get_config("smollm-360m")
    engine = _engine(cfg, "auto")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tree = jax.eval_shape(lambda p: engine.init(p, 2), params)
    assert engine.carry(tree) == "leaf", "one chip must carry the leaves"
    compiled = type(engine).run_phase.lower(
        engine, tree, tree_stack(_batches(cfg, 2, 2, 128))).compile()
    assert "tpu_custom_call" not in compiled.as_text(), \
        "a Mosaic kernel in the leaf-carry phase"
    mem = compiled.memory_analysis()
    print("[smoke] full-width bf16 phase carries the leaves; arguments "
          f"{mem.argument_size_in_bytes} B, temp "
          f"{mem.temp_size_in_bytes} B", flush=True)


def _rounding_on_chip(jax):
    """The dtype rounding on the chip against numpy's casts, bit for bit:
    ``avg_disp.round_codes`` inside a Mosaic kernel, for a one-dtype
    plane's static code and a mixed plane's code row, and its jnp twin
    ``ref.round_to_codes`` compiled by XLA inside a fusion. Inputs span
    f16 subnormals, ties, f16 overflow and inf; f32 denormals are left
    out (the TPU flushes them)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from repro.kernels.avg_disp import round_codes
    from repro.kernels.ref import round_to_codes
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(8 * 4096 - 16)
         * 10.0 ** rng.uniform(-9, 6, 8 * 4096 - 16)).astype(np.float32)
    edges = np.array([0.0, -0.0, np.inf, -np.inf, 65504.0, 65519.0,
                      65520.0, -65520.0, 2 ** -24, 1.5 * 2 ** -24,
                      2.5 * 2 ** -24, 2 ** -25, 1 + 2 ** -11,
                      1 + 3 * 2 ** -11, 1 + 2 ** -8, 3.4e38], np.float32)
    x = np.concatenate([x, edges]).reshape(8, 4096)
    shape = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    for code, dt in ((1, jnp.bfloat16), (2, np.float16)):
        def static(x_ref, o_ref):
            o_ref[...] = round_codes(x_ref[...], code)

        def row(x_ref, c_ref, o_ref):
            o_ref[...] = round_codes(x_ref[...], c_ref[...])

        with np.errstate(over="ignore"):  # to inf past f16's range
            want = x.astype(dt).astype(np.float32).view(np.int32)
        for what, got in (
                ("kernel, static code", pl.pallas_call(
                    static, out_shape=shape)(x)),
                ("kernel, code row", pl.pallas_call(row, out_shape=shape)(
                    x, np.full((1, x.shape[1]), code, np.float32))),
                # + (-0.0) is the identity; an argument, so it stays a
                # runtime add and the rounding sits inside its fusion
                ("jnp twin", jax.jit(
                    lambda a, b: round_to_codes(a + b, code))(
                        x, np.full_like(x, -0.0)))):
            np.testing.assert_array_equal(
                np.asarray(got).view(np.int32), want,
                err_msg=f"{np.dtype(dt).name}: {what}")
    print("[smoke] bf16 and f16 rounding equal the casts bit for bit "
          "(kernel with static code and code row; jnp twin)", flush=True)


#: the relative gaps of loss and dispersion that ``bench/check.py``
#: allows the one-chip cell against its reference
BENCH_RTOLS = (6e-4, 0.03)


def _compare(a, b, what: str, rtols=(1e-5, 1e-4)):
    import numpy as np
    assert a["averages"] == b["averages"] == 3, (a["averages"],
                                                 b["averages"])
    for key, rtol in zip(("loss", "disp_trace"), rtols):
        x = np.array([v for _, v in a[key]])
        y = np.array([v for _, v in b[key]])
        assert np.all(np.isfinite(x)) and np.all(np.isfinite(y)), key
        np.testing.assert_allclose(x, y, rtol=rtol, err_msg=key)
        print(f"[smoke] {what} {key}: max rel diff "
              f"{float(np.max(np.abs(x - y) / np.abs(y)))!r}", flush=True)


def one_chip(jax):
    _train_full_width(jax, 2)
    _full_width_phase(jax)
    _rounding_on_chip(jax)
    for dtype in ("bfloat16", "float32"):
        plane = _reduced_plane_run(jax, 4, dtype, "auto")
        _compare(plane, _reduced_plane_run(jax, 4, dtype, "ref"),
                 f"plane carry, pallas vs ref ({dtype})")
        _compare(_reduced_run(jax, 4, "auto", dtype=dtype), plane,
                 f"leaf carry vs plane carry ({dtype})",
                 **({"rtols": BENCH_RTOLS} if dtype == "bfloat16" else {}))


def _reduced_plane_run(jax, workers: int, dtype: str, kernel_impl: str):
    """:func:`_reduced_run`'s six steps on the plane carry, with the
    Pallas kernels (``kernel_impl`` "auto") or their jnp twins ("ref"):
    one chip's rule carries the leaves, so the plane is asked for by its
    layout and driven a phase at a time."""
    from repro.core import FlatOptSpec, FlatSpec, tree_stack
    cfg, params = _reduced(jax, dtype)
    engine = _engine(cfg, kernel_impl)
    state = engine.init(params, workers)
    spec = FlatSpec.of(state.worker_params)
    layout = (spec, FlatOptSpec.of(spec, state.opt_state))
    state = engine.to_planes(layout, state)
    batches = _batches(cfg, workers, 6, 64)
    hist = {"loss": [], "disp_trace": [], "averages": 0}
    with jax.default_matmul_precision("highest"):
        for t in range(0, 6, 2):
            state, tr = engine.run_phase(state, tree_stack(batches[t:t + 2]),
                                         layout=layout)
            tr = jax.device_get(tr)
            for i in range(2):
                hist["loss"].append((t + i + 1, float(tr["loss"][i])))
                hist["disp_trace"].append(
                    (t + i + 1, float(tr["dispersion"][i])))
                hist["averages"] += int(tr["avg_code"][i] != 0)
    return hist


def four_chips(jax):
    from repro.launch.mesh import make_worker_mesh
    workers = 8
    _, state = _train_full_width(
        jax, workers, ["--shard", "--collective", "psum"])
    for leaf in jax.tree.leaves(state.worker_params):
        shards = leaf.addressable_shards
        assert len(shards) == 4, len(shards)
        assert all(s.data.shape[0] == workers // 4 for s in shards), \
            [s.data.shape for s in shards]
    print(f"[smoke] every device holds {workers // 4} worker rows",
          flush=True)
    h_sharded = _reduced_run(jax, workers, "auto",
                             mesh=make_worker_mesh(workers))
    h_single = _reduced_run(jax, workers, "auto")
    _compare(h_sharded, h_single, "psum sharded vs one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded path over 4 chips")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}, compile "
          f"cache {enable_compile_cache()}", flush=True)
    (four_chips if args.chips == 4 else one_chip)(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
