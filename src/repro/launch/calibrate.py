"""`repro calibrate` — measure and fit this backend's cost-model
constants into a CalibrationProfile.

  python -m repro calibrate [--device tpu-v5e] [--out profile.json] \
      [--fake-devices 8] [--quick] [--matmul-sizes 64,128,...] \
      [--bw-mib 0.25,1,4] [--repeats 3]

Three timed sweeps (repro.calibrate.bench) feed three fits
(repro.calibrate.fit):

  1. square matmuls over a size ladder  -> EfficiencyCurve
     (achieved fraction of peak vs log-flops),
  2. all-gathers over a message-size ladder per mesh axis
     -> per-level LinkCalibration (alpha + bytes/bandwidth),
  3. grad of a matmul chain, plain vs jax.checkpoint -> remat factor.

The profile is normalized against `--peak-flops` when given (fractions
of a datasheet peak), else against the best achieved matmul rate.  On
CPU emulation the numbers calibrate the emulation backend — exactly
what `benchmarks/calibration.py` needs to make predicted-vs-measured
step times comparable; on real hardware the same sweeps calibrate the
chip.  The JSON written by `--out` round-trips through
`CalibrationProfile.load` and plugs into `CostEnv(..., profile=...)`
or `repro.calibrate.store.register`.

Like perf_probe, XLA_FLAGS is set inside main() before the first jax
import, so importing this module leaves the environment untouched.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _csv_ints(text: str):
    return tuple(int(x) for x in text.split(",") if x)


def _csv_floats(text: str):
    return tuple(float(x) for x in text.split(",") if x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro calibrate")
    ap.add_argument("--device", default="host",
                    help="profile name: a DeviceInfo preset to "
                         "calibrate against, or a free name for this "
                         "backend (default: host)")
    ap.add_argument("--out", default=None, metavar="PROFILE_JSON",
                    help="write the fitted CalibrationProfile here")
    ap.add_argument("--fake-devices", type=int, default=8,
                    help="host devices to emulate for the collective "
                         "sweep (XLA_FLAGS, set before jax imports)")
    ap.add_argument("--matmul-sizes", type=_csv_ints,
                    default=(64, 128, 256, 512, 1024))
    ap.add_argument("--bw-mib", type=_csv_floats,
                    default=(0.25, 1.0, 4.0, 16.0))
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--peak-flops", type=float, default=0.0,
                    help="normalize the efficiency curve against this "
                         "peak instead of the best achieved rate")
    ap.add_argument("--remat-depth", type=int, default=8)
    ap.add_argument("--remat-width", type=int, default=256)
    ap.add_argument("--quick", action="store_true",
                    help="small sweeps (CI / smoke): 3 matmul sizes, "
                         "2 message sizes, 1 repeat")
    args = ap.parse_args(argv)

    if args.quick:
        args.matmul_sizes = args.matmul_sizes[:3]
        args.bw_mib = args.bw_mib[:2]
        args.repeats = 1

    # must land before the first jax import (same contract as
    # perf_probe); setdefault lets callers force their own count
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.fake_devices}")

    import jax
    from repro.calibrate import bench, fit
    from repro.calibrate.profile import CalibrationProfile
    from repro.launch.mesh import make_mesh

    t0 = time.perf_counter()

    # 1. compute: matmul ladder -> efficiency curve
    mm = bench.matmul_sweep(args.matmul_sizes, repeats=args.repeats)
    peak = args.peak_flops or bench.measured_peak_flops(mm)
    curve = fit.fit_efficiency_curve(mm, peak_flops=peak)

    # 2. collectives: all-gather ladder per mesh axis -> link fits.
    # Axis names match ClusterSpec.from_flat's level names so the
    # fitted links bind by name on flat specs (and positionally,
    # innermost-first, elsewhere).
    n_dev = len(jax.devices())
    links = ()
    if n_dev >= 2:
        mesh = make_mesh((n_dev,), ("data",))
        sweeps = bench.collective_sweep(mesh, args.bw_mib,
                                        repeats=args.repeats)
        links = fit.fit_link_calibrations(sweeps)

    # 3. remat: plain vs checkpointed grad step -> recompute factor
    t_plain, t_remat = bench.remat_sweep(
        depth=args.remat_depth, width=args.remat_width,
        repeats=args.repeats)
    remat = fit.fit_remat_factor(t_plain, t_remat)

    profile = CalibrationProfile(
        device=args.device, efficiency=curve, links=links,
        remat_factor=remat, peak_flops=peak,
        source=f"repro calibrate ({jax.default_backend()}, "
               f"{n_dev} devices, repeats={args.repeats})")

    # the round-trip guarantee the planner relies on
    assert CalibrationProfile.from_json(profile.to_json()) == profile

    rec = {
        "profile": profile.to_dict(),
        "measured": {
            "matmul": [{"flops": f, "seconds": s} for f, s in mm],
            "peak_flops": peak,
            "remat_plain_s": t_plain,
            "remat_remat_s": t_remat,
        },
        "elapsed_s": time.perf_counter() - t0,
    }
    if args.out:
        profile.save(args.out)
        rec["out"] = args.out
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
