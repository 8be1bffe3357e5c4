"""Readings that set a sharded train cell's limits; the benchmark's runs
never call this. `control.py` cannot take them: its reference lies on
one chip, and a model that needs four chips does not fit there.

    python chipbench/control_sharded.py --workload <cell> \
        --seeds 11,12,13 --faults 11

For each of `--seeds` it takes a sound reading: the program's checked
steps against the float32 reference, compared as a run compares them.
For each of `--faults` it also compares with that reference:

- `control`: the reference with every matmul in float8 (e4m3 operands
  forward, e5m2 gradients backward), the precision below the
  configuration's bfloat16;
- `half_batch`: the reference with half of each batch left out;
- `first_rows`: the exchange left out, as the first chip sees it: each
  chip steps with the gradient of its own rows, so the first chip's
  copy of every tensor it holds whole moves by the gradient of its
  quarter of the batch alone, the reference with the other rows left
  out.

A step that returns its state unchanged needs no run: its change is 0,
so `leaf_change` reads 1. Every reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import compare, gen, run as harness  # noqa: E402
from chipbench.drive_train import train_numbers  # noqa: E402
from chipbench.drive_train_sharded import (Program, first_steps,  # noqa: E402
                                           reference_steps)


def program_reading(ctx, prog: Program, step, seed: int, host) -> dict:
    """The program's checked steps from the seed's weights, through
    `step`, with its state freed afterwards."""
    params, opt = prog.make_state(ctx.family, ctx.model, seed)
    params, opt, first = first_steps(
        prog, step, params, opt, [prog.put(b) for b in host],
        ctx.mix["optimizer"], ctx.family, ctx.model, seed)
    del params, opt
    return first


def line(seed: int, variant: str, ctx, other: dict, ref: dict) -> None:
    numbers = train_numbers(other, ref)
    correct, _ = compare.judge(numbers, ctx.limits)
    print(json.dumps({"seed": seed, "variant": variant, "correct": correct,
                      "numbers": numbers, "losses": other["losses"],
                      "ref_losses": ref["losses"],
                      "gnorms": other["gnorms"],
                      "ref_gnorms": ref["gnorms"]}), flush=True)


def readings(ctx, seeds, faults) -> None:
    """Every reading of the cell `ctx` runs, one JSON line each."""
    m, mix = ctx.model, ctx.mix
    opt_cfg = mix["optimizer"]
    prog = Program(ctx.cfg, m, mix, ctx.devices[:ctx.chips])
    params, opt = prog.make_state(ctx.family, m, 0)
    step = prog.compile(opt_cfg, params, opt, prog.put(
        gen.train_batch(mix, m["vocab_size"], 0, 0)))
    del params, opt
    for seed in seeds:
        host = [gen.train_batch(mix, m["vocab_size"], seed, i)
                for i in range(mix["check_steps"])]
        sound = program_reading(ctx, prog, step, seed, host)
        ref = reference_steps(m, opt_cfg, seed, host, ctx, prog.mesh)
        line(seed, "sound", ctx, sound, ref)
        if seed not in faults:
            continue
        rows = mix["batch"] // ctx.chips
        for variant, kw in (("control", dict(lowp=jnp.float8_e4m3fn)),
                            ("half_batch",
                             dict(rows=slice(0, mix["batch"] // 2))),
                            ("first_rows", dict(rows=slice(0, rows)))):
            other = reference_steps(m, opt_cfg, seed, host, ctx, prog.mesh,
                                    **kw)
            line(seed, variant, ctx, other, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"control_sharded: needs {cell['chips']} TPU chips",
              file=sys.stderr)
        return 3
    harness.configure_cache(jax)
    readings(harness.Context(bench, cell, 0, 0.0, False, devices),
             [int(s) for s in args.seeds.split(",")],
             {int(s) for s in args.faults.split(",") if s})
    return 0


if __name__ == "__main__":
    sys.exit(main())
