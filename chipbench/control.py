"""Readings that set a train cell's limits; the benchmark's runs never
call this.

    python chipbench/control.py --workload <cell> --seeds 11,12,13

For each seed it runs the reference's first steps twice more in the
program's place and compares each with the float32 reference, as a run
compares the program, and judges the numbers against the cell's limits:

- `control`: the same step with every matmul in float8 (e4m3 operands
  forward, e5m2 gradients backward), the precision below the
  configuration's bfloat16;
- `half_batch`: half of each batch left out, the mean taken over the
  rest.

A step that returns its state unchanged needs no run: its change is 0,
so `leaf_change` reads 1.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as harness  # noqa: F401  (puts the checkout on sys.path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import compare, gen  # noqa: E402
from chipbench.drive_train import reference_steps, train_numbers  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    harness.configure_cache(jax)
    ctx = harness.Context(bench, cell, 0, 0.0, False, devices)
    m, mix = ctx.model, ctx.mix
    variants = {"control": dict(lowp=jnp.float8_e4m3fn),
                "half_batch": dict(rows=slice(0, mix["batch"] // 2))}
    for seed in (int(s) for s in args.seeds.split(",")):
        host = [gen.train_batch(mix, m["vocab_size"], seed, i)
                for i in range(mix["check_steps"])]
        ref = reference_steps(m, mix["optimizer"], seed, host, ctx)
        for name, kw in variants.items():
            other = reference_steps(m, mix["optimizer"], seed, host, ctx, **kw)
            numbers = train_numbers(other, ref)
            correct, _ = compare.judge(numbers, ctx.limits)
            print(json.dumps({"seed": seed, "variant": name,
                              "correct": correct, "numbers": numbers,
                              "losses": other["losses"],
                              "ref_losses": ref["losses"],
                              "gnorms": other["gnorms"],
                              "ref_gnorms": ref["gnorms"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
