"""One run of one benchmark cell, on the chips the cell asks for.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name.  ``BENCHMARK.json`` names its configuration
and traffic mix; the configuration is ``bench/configs/<config>.json``; the
mix is ``bench/traffic/<traffic>.json``, whose ``kind`` names the module
``bench/kinds/<kind>.py`` that runs it; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A run sets up and warms up every shape the
window uses (``setup_s``, from process start), measures for ``--seconds``
seconds of whole units of work, reads the device's peak memory, frees the
program's state, and checks what the window produced against the plain
reference.  With ``--trace 1`` the window runs under the profiler and the
metrics are the per-layer ones; with ``--trace 0`` they are the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, with its limit), which also
close standard error.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the script's own directory would shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != BENCH]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """The module in ``path`` under ``bench``, as ``bench.<dir>.<stem>``
    (dots and dashes of the stem made underscores), loaded once."""
    name = "bench.{}.{}".format(path.parent.name, path.stem.replace(
        ".", "_").replace("-", "_"))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def cell(name: str, spec: dict | None = None) -> dict:
    """Everything ``BENCHMARK.json`` and the files it names say about the
    cell ``name``."""
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    wl = found[0]

    def applies(m):
        return name in m.get("workloads", [name])

    return {"workload": wl,
            "config": load_json(BENCH / "configs" / f"{wl['config']}.json"),
            "mix": load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def use_compile_cache() -> str:
    """jax's persistent compilation cache in ``JAX_COMPILATION_CACHE_DIR``
    or the checkout's fixed ``.jax_cache``, with every program written to
    it however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(n_chips: int):
    """The first ``n_chips`` TPU devices; exits when there are fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} TPU chip(s); jax "
                         f"found {len(devices)} {devices[0].platform} "
                         "device(s)")
    return devices[:n_chips]


def run_cell(c: dict, seed: int, seconds: float, trace: bool, devices, *,
             start: float, trace_dir: str | None = None) -> dict:
    """Set up, measure, check; returns the result object (printed by
    :func:`main` only).  ``start`` is the clock reading ``setup_s`` counts
    from."""
    import jax

    from bench import deploy
    from bench import trace as tr

    clock = deploy.CompileClock()
    kind = load_module(BENCH / "kinds" / f"{c['mix']['kind']}.py")
    run = kind.Cell(c["config"], c["mix"], seed, devices,
                    jax.profiler.TraceAnnotation)
    run.setup()
    setup_s = time.perf_counter() - start
    clock.reset()
    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tmp)
    try:
        with jax.profiler.TraceAnnotation("window"):
            out = run.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = clock.count
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    run.release()
    gc.collect()

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result: dict = {"attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        reduced = tr.reduce(tr.find_trace(tmp), len(devices))
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        rec = {"units": out["units"], "compiles": compiles,
               "cache": out.get("cache"), "trace": reduced,
               "work": run.work(), "device_kind": d0.device_kind}
        metrics = {}
        for m in c["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py"
                                ).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = sum(reduced["busy_s"]) / len(reduced["busy_s"])
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    else:
        e2e = dict(out["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    c0 = time.perf_counter()
    checks = run.check()
    check_s = time.perf_counter() - c0
    result.update(correct=all(v <= lim for _, v, lim in checks),
                  metrics=metrics, device=device,
                  checks={n: {"value": v, "limit": lim}
                          for n, v, lim in checks})
    result["_log"] = {"e2e": out["e2e"], "compiles": compiles,
                      "setup_s": setup_s, "check_s": check_s}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw trace in this directory")
    args = ap.parse_args(argv)
    c = cell(args.workload)
    cache = use_compile_cache()
    devices = tpu_devices(int(c["workload"]["chips"]))
    print(f"bench: {args.workload} seed {args.seed} on "
          f"{devices[0].device_kind} x{len(devices)}, compile cache "
          f"{cache}", flush=True)
    result = run_cell(c, args.seed, args.seconds, bool(args.trace), devices,
                      start=START, trace_dir=args.trace_dir)
    log = result.pop("_log")
    print(f"bench: window {json.dumps(log['e2e'])}, setup_s "
          f"{log['setup_s']!r}, compiles in window {log['compiles']}, "
          f"check_s {log['check_s']!r}",
          flush=True)
    checks = result.pop("checks")
    result["checks"] = checks
    for name, c_ in checks.items():
        print(f"check {name}: {c_['value']!r} limit {c_['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
