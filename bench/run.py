#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It finds the cell in
``BENCHMARK.json``, the cell's configuration in the file its entry
names, its traffic in ``bench/traffic/<traffic>.json`` and, with
``--trace 1``, each per-layer metric's reader in
``bench/metrics/<metric>.py``.  The traffic file names its loop
(``bench/loops/<loop>.py``), which makes the weights and the
inputs from ``--seed``, sets up, warms up every shape the traffic
uses, measures for ``--seconds`` and then checks what the timed path
produced against the plain reference.

It runs on a TPU or not at all: with no TPU, too few chips, or
``REPRO_KERNEL_IMPL`` pinning anything but ``pallas`` it exits non-zero
and prints no result.  The last lines on standard error are the
numbers compared, each with its limit; the last line on standard
output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace of the
first seconds of the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: Where a run writes its profiler trace (removed once read).
OUT_DIR = os.path.join(HERE, "out")


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(bench: dict, workload: str):
    """The cell's entry, its configuration entry and file, its traffic
    file, and the end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return cell, cfg, traffic, e2e, layer


class CompileCounter:
    """Counts programs traced and compiled (or fetched from the
    persistent cache) while it is armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.count += 1


class Context:
    """What a loop gets: the cell, its configuration and traffic, the
    run's arguments, and the harness's clock and compile counter."""

    def __init__(self, cell, cfg, traffic, seed, seconds, trace,
                 t_start, compiles, reference, control=False):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.compiles = compiles
        self.reference = reference
        #: Also compute the control's numbers (``calibrate.py``).
        self.control = control

    def vertex(self):
        """The program's vertex function the configuration names."""
        mod_name, cls = self.cfg["vertex"].split(":")
        mod = importlib.import_module(mod_name)
        return getattr(mod, cls)(**self.cfg["vertex_args"])

    def control_how(self):
        """``(dtype, precision)`` of the control: the reference in the
        precision below the one the configuration states."""
        import jax.numpy as jnp
        c = self.cfg["control"]
        return jnp.dtype(c["dtype"]).type, c["precision"]

    def trace_dir(self) -> str:
        return os.path.join(OUT_DIR, "trace", self.cell["name"])


def read_layer_metrics(layer, record) -> dict:
    """Each per-layer metric's reader over the loop's record; a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in layer:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        value = load_module(path, "metric_" + m["name"].replace(".", "_")
                            ).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def prepare(workload: str, *, bench: dict | None = None,
            cfg_override: dict | None = None,
            traffic_override: dict | None = None,
            require_tpu: bool = True) -> dict:
    """Resolve the cell, start JAX on the chip the cell needs, and load
    the cell's reference and loop."""
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, e2e, layer = resolve_cell(bench, workload)
    cfg = {**cfg, **(cfg_override or {})}
    traffic = {**traffic, **(traffic_override or {})}

    flags = " ".join(cfg.get("libtpu_flags", []))
    if flags:
        prev = os.environ.get("LIBTPU_INIT_ARGS", "")
        os.environ["LIBTPU_INIT_ARGS"] = (prev + " " + flags).strip()
    for p in (os.path.join(ROOT, "src"), HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    # The program's matrix products run at the precision the
    # configuration states (JAX's own default is one bfloat16 pass).
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU found (JAX platform "
                          f"{devs[0].platform!r}); nothing was run")
        if len(devs) < cell["chips"]:
            raise Refused(f"{workload} needs {cell['chips']} chips, JAX "
                          f"sees {len(devs)}")
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        # Cache every program, however fast it compiles, so that only
        # a checkout's first run of a cell compiles.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    reference = load_module(os.path.join(HERE, "reference",
                                         cfg["reference"] + ".py"),
                            "reference_" + cfg["reference"])
    loop = load_module(os.path.join(HERE, "loops",
                                      traffic["loop"] + ".py"),
                         "loop_" + traffic["loop"])
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "e2e": e2e,
            "layer": layer, "reference": reference, "loop": loop,
            "compiles": CompileCounter()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             prepared: dict | None = None, t_start: float | None = None,
             control: bool = False, **prepare_kw):
    """Run one cell and return ``(result, checks, out)``: the result
    line, the numbers compared with their limits, and all the loop
    returned.  Tests pass ``require_tpu=False`` and small overrides to
    drive a whole run on the CPU."""
    pr = prepared or prepare(workload, **prepare_kw)
    cell, cfg, traffic = pr["cell"], pr["cfg"], pr["traffic"]
    ctx = Context(cell, cfg, traffic, seed, seconds, trace,
                  T_START if t_start is None else t_start,
                  pr["compiles"], pr["reference"], control)
    out = pr["loop"].run(ctx)

    if trace:
        metrics = read_layer_metrics(pr["layer"], out["record"])
    else:
        missing = [m["name"] for m in pr["e2e"]
                   if m["name"] not in out["metrics"]]
        if missing:
            raise RuntimeError(f"loop measured no {missing}")
        metrics = {m["name"]: {"value": float(out["metrics"][m["name"]]),
                               "unit": m["unit"]} for m in pr["e2e"]}
    import device as device_mod
    from check import judge
    checks = judge(out["values"], cfg["limits"][traffic["loop"]])
    device = {**device_mod.info(),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(out["sound"] and all(c[3] for c in checks)),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        result["device"]["busy_s"] = out["record"]["trace"]["busy_s"]
        result["device"]["window_s"] = out["record"]["trace"]["window_s"]
        result["breakdown"] = out["record"]["trace"]["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim, _ok in checks}
    return result, checks, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl not in (None, "", "pallas"):
        print(f"bench: REPRO_KERNEL_IMPL={impl!r} pins a non-Pallas "
              f"backend; unset it or set it to 'pallas'", file=sys.stderr)
        return 2
    try:
        result, checks, out = run_cell(args.workload, args.seed,
                                       args.seconds, bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for note in out.get("notes", []):
        print(note, file=sys.stderr)
    for name, value, limit, ok in checks:
        print(f"check {name} {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # Leave at once: nothing the runtime prints on its way out may
    # follow the result lines.
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
