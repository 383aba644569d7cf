#!/usr/bin/env python3
"""Smoke run of the library's main path on a real TPU.

One process, no JAX subprocess, no emulated devices. The default run
needs one chip and drives, through the normal entry points:

1. device     — the first device is a TPU (else exit non-zero);
2. plan       — ``SlabFFTPlan`` 512^3 R2C+C2R, default ``Config`` (the
                platform default: MXU matmuls at HIGHEST on a TPU): the
                analytic spectrum and the roundtrip identity at 512^3,
                and the dense random spectrum against ``numpy.fft.rfftn``
                at 256^3;
3. backends   — the same 512^3 plan on the ``xla`` and ``pallas``
                backends; pallas must compile to a Mosaic kernel;
4. serve      — an in-process ``serve.Server`` answering 2D images and a
                3D volume, each checked against numpy;
5. profile    — ``obs.profile.stage_profile`` of the 512^3 forward on the
                device trace.

``--multichip`` runs only the sharded path on four chips: slab and
pencil plans at 1024^3 (analytic spectrum, roundtrip, outputs and memory
spread over the four devices) and at 256^3 against numpy.

Every check is a relative error against a bound per local backend,
printed beside it. Any failed check or exception exits non-zero. The
last line of standard output is the JSON object the driver reads.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Relative f32 error bounds, per local-FFT backend: max |out - ref| over
# max |ref| (spectra) or over N * max |x| (roundtrips). See CHANGES.md.
BOUNDS = {"xla": 5e-5, "matmul": 5e-4, "pallas": 5e-4}

ITERS = 3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def rel_checks(phase: str, backend: str, plan, *,
               roundtrip: bool = True) -> None:
    """testcase 1 (spectrum) and 3 (roundtrip) of the reference, as
    relative errors against the backend's bound."""
    from distributedfft_tpu.testing import testcases

    bound = BOUNDS[backend]
    r = testcases.testcase1(plan, truth="analytic", write_csv=False)
    rel = r["residual_max"] / r["ref_max"]
    say(phase, check="spectrum_vs_analytic", rel_err=rel, bound=bound)
    check(rel <= bound, f"{phase}: analytic spectrum rel err {rel}")
    if roundtrip:
        r = testcases.testcase3(plan, write_csv=False)
        rel = r["max_error"] / r["ref_max"]
        say(phase, check="roundtrip", rel_err=rel, bound=bound)
        check(rel <= bound, f"{phase}: roundtrip rel err {rel}")


def timed_roundtrip(plan, x):
    """Compile seconds (first call) and steady ms per R2C+C2R through
    ``exec_r2c``/``exec_c2r``, each ending in ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    y = plan.exec_c2r(plan.exec_r2c(x))
    jax.block_until_ready(y)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(ITERS):
        y = plan.exec_c2r(plan.exec_r2c(x))
    jax.block_until_ready(y)
    steady_ms = (time.perf_counter() - t0) * 1e3 / ITERS
    return compile_s, steady_ms, y


def exec_roundtrip_check(tag: str, backend: str, plan, x, y) -> None:
    """``exec_c2r(exec_r2c(x))`` against N * x, reduced on the device(s):
    the fused programs a user calls, beside testcase 3's staged ones."""
    from distributedfft_tpu.testing import sharded

    n_total = float(plan.global_size.n_total)
    _, m = sharded.residuals(plan, y, x, "real", ref_scale=n_total)
    rel = m / n_total  # inputs are uniform in [0, 1)
    bound = BOUNDS[backend]
    say(tag, check="exec_roundtrip", rel_err=rel, bound=bound)
    check(rel <= bound, f"{tag}: exec roundtrip rel err {rel}")


def device_random(plan, seed: int = 0):
    """Uniform [0, 1) input made on the device(s) in the plan's input
    layout — nothing staged through one device."""
    import jax
    import jax.numpy as jnp

    shape = tuple(plan.input_shape)
    gen = jax.jit(lambda k: jax.random.uniform(k, shape, jnp.float32),
                  out_shardings=plan.input_sharding)
    return plan.pad_input(gen(jax.random.key(seed)))


def phase_plan(n: int, backend, tag: str):
    """``backend`` None: the default Config, the platform's backend."""
    import jax

    import distributedfft_tpu as dfft

    plan = dfft.SlabFFTPlan(dfft.GlobalSize(n, n, n), dfft.SlabPartition(1),
                            dfft.Config(fft_backend=backend))
    backend = plan.config.fft_backend
    say(tag, backend=backend, precision=plan.config.mxu_precision,
        direct_max=plan.config.mxu_direct_max)
    x = device_random(plan)
    compile_s, steady_ms, y = timed_roundtrip(plan, x)
    say(tag, shape=f"{n}^3", backend=backend, compile_s=f"{compile_s:.3f}",
        steady_ms=f"{steady_ms:.3f}")
    exec_roundtrip_check(tag, backend, plan, x, y)
    del x, y
    rel_checks(tag, backend, plan)
    say(tag, peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    return plan


def phase_host_truth(n: int, plan_factory, tag: str):
    """Dense random spectrum against numpy.fft.rfftn on the host."""
    from distributedfft_tpu.testing import testcases

    plan = plan_factory(n)
    r = testcases.testcase1(plan, truth="host", write_csv=False)
    rel = r["residual_max"] / r["ref_max"]
    bound = BOUNDS[plan.config.fft_backend]
    say(tag, check=f"spectrum_vs_numpy_{n}^3", rel_err=rel, bound=bound)
    check(rel <= bound, f"{tag}: numpy spectrum rel err {rel}")


def phase_pallas_kernel(plan) -> None:
    from distributedfft_tpu.analysis import hloscan

    for direction in ("forward", "inverse"):
        txt = hloscan.compiled_text(plan, direction)
        has = "tpu_custom_call" in txt
        say("backends", pallas_direction=direction, tpu_custom_call=has)
        check(has, f"pallas {direction}: no Mosaic kernel in the program")


def phase_serve() -> None:
    import numpy as np

    import jax

    import distributedfft_tpu as dfft
    from distributedfft_tpu import serve

    rng = np.random.default_rng(1)
    shapes = [(1024, 1024)] * 4 + [(2048, 2048)] * 3 + [(256, 256, 256)]
    reqs = [(rng.random(s, dtype=np.float32), "forward") for s in shapes]
    # One inverse image and one inverse volume: the C2R path.
    reqs += [(np.fft.rfftn(rng.random(s)).astype(np.complex64), "inverse")
             for s in ((2048, 2048), (256, 256, 256))]
    from distributedfft_tpu.utils import wisdom

    fields, _ = wisdom.platform_default(dfft.Config(),
                                        dfft.GlobalSize(256, 256, 256))
    bound = BOUNDS[fields["fft_backend"]]
    with serve.Server(dfft.SlabPartition(1), dfft.Config(),
                      latency_budget_ms=600_000.0) as srv:
        for label in ("cold", "warm"):
            t0 = time.perf_counter()
            futs = [srv.submit(x, direction=d) for x, d in reqs]
            outs = [f.result(timeout=600) for f in futs]
            say("serve", pass_=label, requests=len(reqs),
                wall_ms=f"{(time.perf_counter() - t0) * 1e3:.3f}")
        for (x, d), y in zip(reqs, outs):
            x64 = x.astype(np.complex128 if d == "inverse" else np.float64)
            ref = (np.fft.rfftn(x64) if d == "forward"
                   else np.fft.irfftn(x64) * np.prod(y.shape))
            check(y.shape == ref.shape, f"serve: shape {y.shape} for {x.shape}")
            rel = float(np.abs(y - ref).max() / np.abs(ref).max())
            say("serve", shape="x".join(map(str, x.shape)), direction=d,
                rel_err=rel, bound=bound)
            check(rel <= bound, f"serve {d} {x.shape}: rel err {rel}")
        h = srv.health()
    say("serve", served=h["counters"]["served"],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


def phase_profile(plan) -> None:
    from distributedfft_tpu.analysis import plangraph
    from distributedfft_tpu.obs import profile

    prof = profile.stage_profile(plan, "forward", 3, iters=ITERS)
    for line in profile.format_stage_profile(prof):
        print("[profile]" + line, flush=True)
    say("profile", planes=",".join(prof["planes"]),
        attributed_ms=prof["attributed_ms"], window_ms=prof["window_ms"])
    graph = plangraph.graph_for(plan, "forward", 3)
    rows = {r["node"]: r for r in prof["stages"]}
    ffts = [n.id for n in graph.nodes if n.kind == "local_fft"]
    check(bool(ffts), "profile: the plan declares no local_fft node")
    for nid in ffts:
        check(rows[nid]["device_ms"] > 0,
              f"profile: local_fft node {nid} got no device time")
    check(any(p.startswith("/device:") for p in prof["planes"]),
          f"profile: no device plane in the trace ({prof['planes']})")
    check(prof["attributed_ms"] <= prof["window_ms"],
          f"profile: attributed {prof['attributed_ms']} ms exceeds the "
          f"traced window {prof['window_ms']} ms")


def run_one_chip() -> None:
    import distributedfft_tpu as dfft

    plan = phase_plan(512, None, "plan")
    phase_host_truth(256, lambda n: dfft.SlabFFTPlan(
        dfft.GlobalSize(n, n, n), dfft.SlabPartition(1), dfft.Config()),
        "plan")
    phase_plan(512, "xla", "backends")
    phase_pallas_kernel(phase_plan(512, "pallas", "backends"))
    phase_serve()
    phase_profile(plan)


def run_multichip() -> None:
    import jax

    import distributedfft_tpu as dfft
    from distributedfft_tpu.parallel import mesh as pmesh

    devs = jax.devices()
    check(len(devs) == 4, f"--multichip needs 4 devices, found {len(devs)}")
    n = 1024

    def slab(m):
        return dfft.SlabFFTPlan(dfft.GlobalSize(m, m, m),
                                dfft.SlabPartition(4), dfft.Config())

    def pencil(m):
        return dfft.PencilFFTPlan(dfft.GlobalSize(m, m, m),
                                  dfft.PencilPartition(2, 2), dfft.Config())

    for tag, make, shape in (("slab4", slab, (4,)),
                             ("pencil4", pencil, (2, 2))):
        path = ("create_device_mesh" if pmesh._topology_mesh(shape)
                is not None else "enumeration")
        plan = make(n)
        backend = plan.config.fft_backend
        say(tag, mesh_path=path,
            mesh_devices=[d.id for d in plan.mesh.devices.flat],
            backend=backend, precision=plan.config.mxu_precision,
            direct_max=plan.config.mxu_direct_max)
        x = device_random(plan)
        compile_s, steady_ms, y = timed_roundtrip(plan, x)
        say(tag, shape=f"{n}^3", compile_s=f"{compile_s:.3f}",
            steady_ms=f"{steady_ms:.3f}")
        c = plan.exec_r2c(x)
        for what, arr in (("spectrum", c), ("roundtrip", y)):
            nd = len(arr.sharding.device_set)
            say(tag, output=what, devices=nd)
            check(nd == 4, f"{tag}: {what} lives on {nd} devices")
        exec_roundtrip_check(tag, backend, plan, x, y)
        del x, y, c
        rel_checks(tag, backend, plan, roundtrip=False)
        peaks = [peak_bytes(d) for d in devs]
        quarter = 4 * n ** 3 // 4  # f32 input bytes per device
        say(tag, peak_bytes_in_use=peaks)
        check(min(peaks) >= quarter,
              f"{tag}: a device peaked below its input quarter: {peaks}")
        check(max(peaks) <= 1.5 * min(peaks),
              f"{tag}: memory not spread over the devices: {peaks}")
        phase_host_truth(256, make, tag)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip slab/pencil phase")
    args = ap.parse_args(argv)

    import jax

    from distributedfft_tpu import obs
    from distributedfft_tpu.parallel.mesh import use_compile_cache

    cache = use_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {d0.platform}); nothing run",
              file=sys.stderr)
        return 2
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache)
    t0 = time.perf_counter()
    if args.multichip:
        run_multichip()
    else:
        run_one_chip()
    demotions = obs.metrics.counter_total("fallback.demotions")
    say("fallback", demotions=demotions)
    check(demotions == 0, f"the fallback ladder demoted {demotions} time(s)")
    say("done", seconds=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
