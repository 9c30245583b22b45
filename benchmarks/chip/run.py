"""Run one cell of ``BENCHMARK.json`` once, on the chip, and print its line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name: the cell's configuration in ``configs/``,
its traffic mix in ``traffic/`` (whose ``loop`` names the loop module, and
whose ``kernel`` names the ops-and-bytes function in ``work/``), each
metric's reader in ``metrics/<name before the first '.'>.py``, and the
chip's peaks in ``peaks.json`` by ``device_kind``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit.  Those also end standard error.
A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]
CACHE_DIR = CHECKOUT / ".jax_cache"
sys.path.insert(0, str(CHECKOUT / "benchmarks"))
sys.path.insert(0, str(CHECKOUT / "src"))


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (a
    fixed path: the path is part of the cache's key), whatever the
    environment names, with no size limit: the LRU eviction a limit turns
    on stops caching at the first entry written without its access-time
    file."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def load_spec() -> dict:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_plan(spec: dict, name: str) -> dict:
    """The cell, its configuration and mix, and the metrics it reports."""
    from chip import traffic

    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(CHECKOUT / conf["file"]) as f:
        cfg = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "cfg": cfg,
            "mix": traffic.load_mix(cell["traffic"]),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def device_info(chips: int) -> tuple[dict, dict]:
    """(device fields of the result line, the chip's peaks); raises
    :class:`NoChip` unless JAX sees at least ``chips`` TPUs."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({kind})")
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json")
    return ({"platform": devs[0].platform, "kind": kind,
             "device_kind": kind, "count": len(devs)}, peaks[kind])


class Tracer:
    """The profiler over the measured window, reduced by chip.trace."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chip-bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax

        from chip import trace

        jax.profiler.stop_trace()
        try:
            return trace.reduce(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_metrics(entries: list[dict], rec) -> dict:
    """Each metric from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        reader = importlib.import_module(
            f"chip.metrics.{m['name'].split('.')[0]}")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(plan: dict, seed: int, seconds: float, trace_on: bool,
            device: dict, peak: dict, prog=None):
    """Drive the cell's loop; returns its record."""
    loop = importlib.import_module(f"chip.{plan['mix']['loop']}")
    return loop.run(plan["cfg"], plan["mix"], seed, seconds,
                      chips=plan["cell"]["chips"], peak=peak,
                      t_start=T_START,
                      tracer=Tracer() if trace_on else None, prog=prog)


def result_line(plan: dict, rec, device: dict, trace_on: bool) -> dict:
    correct = all(v <= limit for v, limit in rec.checks.values())
    metrics = read_metrics(plan["per_layer" if trace_on else "end_to_end"],
                           rec)
    dev = dict(device, memory_peak_bytes=rec.memory_peak)
    line = {"correct": correct, "attempted": rec.attempted,
            "failed": rec.failed, "metrics": metrics, "device": dev}
    if trace_on and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        line["breakdown"] = rec.trace.breakdown()
    line["checks"] = {k: {"value": v, "limit": limit}
                      for k, (v, limit) in rec.checks.items()}
    return line


def _finite(x):
    """JSON has no infinity: a latency that never ended prints as 1e300."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    plan = cell_plan(load_spec(), args.workload)
    enable_cache()
    try:
        device, peak = device_info(plan["cell"]["chips"])
    except NoChip as e:
        print(f"error: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    device_ready_s = time.perf_counter() - T_START
    rec = execute(plan, args.seed, args.seconds, bool(args.trace),
                  device, peak)
    line = result_line(plan, rec, device, bool(args.trace))
    print(f"cell {args.workload} seed {args.seed} setup_s "
          f"{rec.setup_s:.3f} device_ready_s {device_ready_s:.3f} window_s {rec.window_s:.3f} attempted "
          f"{rec.attempted} failed {rec.failed} roofline_bound "
          f"{rec.bounds or None}", flush=True)
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
