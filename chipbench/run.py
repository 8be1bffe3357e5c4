"""The chip benchmark: one cell of `BENCHMARK.json`, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell from the files its names point to (the configuration
under `configs/`, the traffic mix under `traffic/`, the limits of its
correctness check under `limits/`), runs `drive_<kind>.py` for the
mix's `kind`: set-up, a window of `--seconds`, then the
comparison with the reference. With `--trace 0` it reports the cell's
end-to-end metrics; with `--trace 1` it traces the window and reports
its per-layer metrics, each read by `metrics/<name>.py`. The last line
of standard output is one JSON object. Without an accelerator, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, trace as tr  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

HERE = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"


def since_process_start() -> float:
    """Seconds since this process was started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class NoAccelerator(RuntimeError):
    pass


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"),
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Context:
    """What a drive module gets: the cell's files, the run's arguments, the
    harness's hooks for set-up, the window and the reference."""

    files = HERE

    def __init__(self, bench: dict, cell: dict, seed: int, seconds: float,
                 trace: bool, devices, root: pathlib.Path = ROOT):
        self.bench, self.cell = bench, cell
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = cell["chips"]
        self.devices = devices
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.cfg = load_json(root / conf["file"])
        self.family = importlib.import_module(
            f"chipbench.families.{self.cfg['family']}")
        self.model = self.family.program_kwargs(self.cfg)
        self.mix = load_json(self.files / "traffic" /
                             f"{cell['traffic']}.json")
        self.limits = load_json(self.files / "limits" /
                                f"{cell['name']}.json")
        self.setup_s = math.nan
        self.trace_data = None

    def planted(self, timed):
        """What the window drives, the compiled train step: as it is,
        except where a test or `control.py` plants a fault or the control
        here."""
        return timed

    def setup_done(self) -> None:
        self.setup_s = since_process_start()

    @contextlib.contextmanager
    def window(self):
        import jax
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if self.trace \
            else None
        if tmp:
            jax.profiler.start_trace(tmp)
        try:
            with tr.span("window"):
                clock = Clock()
                yield clock
        finally:
            if tmp:
                jax.profiler.stop_trace()
                try:
                    self.trace_data = tr.load(tr.find_xplane(tmp))
                finally:
                    shutil.rmtree(tmp, ignore_errors=True)

    def memory_peak(self, mem) -> float:
        """Peak bytes on the fullest chip: the larger of what the runtime
        counted and what the compiler laid out for the step."""
        from chipbench.drive_train import compiled_bytes
        seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.devices[:self.chips]]
        compiled = compiled_bytes(mem) if mem is not None else 0.0
        return float(max(seen + [compiled if math.isfinite(compiled)
                                 else 0.0]))


def metric_rows(bench: dict, cell: str, kind: str) -> list:
    """The cell's metrics of `kind` (`end_to_end` or `per_layer`): those
    that list it, and those that list no cells and move an end-to-end
    metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    rows = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                rows.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            rows.append(m)
    return rows


def execute(bench: dict, cell_name: str, seed: int, seconds: float,
            trace: bool, devices, context_cls=Context) -> dict:
    cell = next((w for w in bench["workloads"] if w["name"] == cell_name),
                None)
    if cell is None:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    if len(devices) < cell["chips"]:
        raise NoAccelerator(f"{cell_name} needs {cell['chips']} chips, "
                            f"found {len(devices)}")
    ctx = context_cls(bench, cell, seed, seconds, trace, devices)
    drive = importlib.import_module(f"chipbench.drive_{ctx.mix['kind']}")
    res = drive.run(ctx)
    correct, checks = compare.judge(res["numbers"], ctx.limits)
    failed = res["failed"]
    metrics = {}
    if not trace:
        values = dict(res["end_to_end"], setup_s=ctx.setup_s)
        for m in metric_rows(bench, cell_name, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = devices[0]
    out = {"correct": bool(correct and failed == 0),
           "attempted": res["attempted"], "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": res["memory"]}}
    if trace:
        t = ctx.trace_data
        facts = dict(res["facts"], chips=cell["chips"],
                     peak_flops=peaks_for(dev.device_kind)["bf16_flops"])
        for m in metric_rows(bench, cell_name, "per_layer"):
            v = load_reader(m["name"])(facts, t)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = t.window()
        out["device"].update(busy_s=tr.busy_s(t), window_s=hi - lo)
        out["breakdown"] = {"device_ops": tr.top_ops(t),
                            "idle_gaps": tr.idle_gaps(t)}
    out["detail"] = dict(res.get("detail", {}), not_compared={
        k: v for k, v in res["numbers"].items() if k not in ctx.limits})
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def configure_cache(jax) -> None:
    """JAX's persistent compilation cache in `.jax_cache/` at the root of
    this checkout, a fixed path, whatever the environment names, so that
    two checkouts share no compiled program; the program is handed the
    same directory. Every program goes in, however fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no accelerator (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 3
    configure_cache(jax)
    try:
        out = execute(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    gc.collect()
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
