"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(or ``python3 -m bench.run ...``) from the root of a checkout, on a host
with the chips the cell asks for.  The cell, its configuration and its
traffic are found by name from ``BENCHMARK.json``: the configuration's
``file``, the traffic's ``bench/workloads/<traffic>.json``, the driver
``bench/drivers/<driver>.py`` that the configuration names, and one reader
``bench/metrics/<metric>.py`` per per-layer metric.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the plain
reference, beside its limit.  The same numbers are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits with 2 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Run as a script, Python puts bench/ itself first on the path, where
# bench/trace.py would shadow the standard library's trace module.
sys.path[:] = [str(ROOT)] + [q for q in sys.path if Path(q or ".").resolve() != ROOT / "bench"]

from bench import harness, trace  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: JAX's persistent compilation cache, at a fixed path inside the checkout
#: unless the environment names one.
CACHE_DIR = ROOT / ".jax_cache"


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(man: dict, name: str) -> harness.Cell:
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json ({', '.join(cells)})")
    w = cells[name]
    config = {c["name"]: c for c in man["configs"]}[w["config"]]
    return harness.Cell(
        name=name,
        config=json.loads((ROOT / config["file"]).read_text()),
        traffic=json.loads((ROOT / "bench" / "workloads" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
    )


def reported(man: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics, or traced, the
    per-layer metrics that list it.  A metric without a ``workloads`` list
    is reported by every cell (an end-to-end metric) or by every cell that
    reports the end-to-end metric it moves (a per-layer metric), as the
    manifest's rules have it for entries that later cells inherit."""
    def listed(m):
        return cell in m["workloads"] if "workloads" in m else None

    e2e = [m for m in man["end_to_end"] if listed(m) is not False]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in man["per_layer"] if (listed(m) if "workloads" in m else m["moves"] in names)]


def reader(metric: str):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.eprint(f"bench: no TPU, JAX's backend is {devices[0].platform!r}")
        raise SystemExit(2)
    if len(devices) < chips:
        harness.eprint(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
        raise SystemExit(2)
    return devices


def import_program() -> None:
    """The system under test, from this checkout's ``src``."""
    src = ROOT / "src"
    if not (src / "repro" / "engine" / "queries.py").is_file():
        harness.eprint(f"bench: the program is not at {src}")
        raise SystemExit(2)
    sys.path.insert(1, str(src))


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    man = manifest()
    cell = load_cell(man, args.workload)
    devices = require_chips(cell.chips)
    import_program()
    enable_compile_cache()
    driver = importlib.import_module(f"bench.drivers.{cell.config['driver']}")
    return finish(man, cell, devices, args, driver.run)


def finish(man, cell, devices, args, run) -> int:
    """Drive one run of ``cell`` and print its result; the part of
    :func:`main` after the look for chips (tests call it on the CPU)."""
    spans = trace.Spans()
    window = harness.Window(spans, traced=bool(args.trace))
    out = run(cell, args.seed, args.seconds, window, spans)
    setup_s = out.window_start - PROCESS_START
    harness.say(setup="total", seconds=setup_s)
    used = devices[: cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    try:
        checks = out.verify()
        failed = out.failed(checks)
    except Exception:  # noqa: BLE001 - a check that cannot run is a failed check
        traceback.print_exc()
        checks = [harness.Check("reference_ran", 1.0, 0.0)]
        failed = out.attempted
    kind = used[0].device_kind
    reading = harness.Reading(cell, kind, out.records, spans, window.load_trace())
    metrics = {}
    for m in reported(man, cell.name, bool(args.trace)):
        if args.trace:
            value = reader(m["name"])(reading)
        else:
            value = setup_s if m["name"] == "setup_s" else out.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind, "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": out.attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reading.trace is not None:
        device.update(busy_s=reading.trace.busy_s(), window_s=reading.trace.window_s)
        result["breakdown"] = trace.breakdown(reading.trace)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        harness.eprint(f"check {c.name} = {c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
