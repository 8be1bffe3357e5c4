"""A traced run of a train cell, with the device's self time split by pass
and by program scope.

    python chipbench/scoped_run.py --workload <cell> --seed <n> \
        --seconds <s>

Runs the cell as `run.py --trace 1` does (set-up, the traced window, the
comparison with the reference) and prints its result line, to which it
adds `detail.device_scopes` (ms per window step for each pass and scope,
with `none`, `unattributed` and `other_modules` as they come),
`scope_metrics` (the per-step numbers of `scopes.METRICS`) and
`scope_cost_s` (the seconds taken to read the compiled step's text and
to reduce the trace). Without an accelerator it exits with code 3.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, scopes, trace as tr  # noqa: E402


class ScopedContext(run.Context):
    """The harness's context, which also keeps the compiled step's
    instruction map and each device's module events."""

    def planted(self, timed):
        t0 = time.perf_counter()
        text = timed.as_text()
        self.scope_ops = scopes.op_names(text)
        self.step_module = scopes.module_name(text)
        self.as_text_s = time.perf_counter() - t0
        return timed

    @contextlib.contextmanager
    def window(self):
        import jax
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp)
        try:
            with tr.span("window"):
                yield run.Clock()
        finally:
            jax.profiler.stop_trace()
            try:
                self.trace_data = scopes.load(tr.find_xplane(tmp))
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


def scoped(out: dict, ctx: ScopedContext) -> dict:
    """The run's result line with the scope split added."""
    t0 = time.perf_counter()
    times = scopes.scope_times(ctx.trace_data, ctx.scope_ops,
                               ctx.step_module)
    reduce_s = time.perf_counter() - t0
    steps = out["attempted"]
    out["detail"]["device_scopes"] = scopes.table(times, steps)
    out["scope_metrics"] = {name: scopes.metric(name, times, steps)
                            for name in scopes.METRICS}
    out["scope_cost_s"] = {"as_text": ctx.as_text_s, "reduce": reduce_s}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(ROOT / "BENCHMARK.json")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"scoped_run: no accelerator (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 3
    run.configure_cache(jax)
    made = []

    def context(*a):
        made.append(ScopedContext(*a))
        return made[-1]

    try:
        out = run.execute(bench, args.workload, args.seed, args.seconds,
                          True, devices, context_cls=context)
    except run.NoAccelerator as e:
        print(f"scoped_run: {e}", file=sys.stderr)
        return 3
    print(json.dumps(scoped(out, made[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
