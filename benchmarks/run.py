"""benchmarks/run.py — one cell of BENCHMARK.json, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A cell names a configuration (``benchmarks/configs/<config>.json``) and a
traffic mix (``benchmarks/traffic/<traffic>.json``); the traffic file names
its driver (``benchmarks/drivers/<driver>.py``); each per-layer metric is
``benchmarks/layer_metrics/<name>.json`` and names its reader
(``benchmarks/readers/<reader>.py``).  Everything is found by name, so a
later cell, mix, metric or reader is new files and no edit here.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and ``checks``
(every number compared, beside its limit).  Without a
TPU of enough chips the run exits 1 and prints no result; ``--rehearse``
runs tiny shapes on the CPU to prove the control flow and prints no
result line either.
"""

import time
T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU; no result line")
    ap.add_argument("--control", action="store_true",
                    help="after the run, also read the control of "
                         "`correct` (the reference in int8); for setting "
                         "limits, never part of a measured run")
    args = ap.parse_args(argv)

    from benchmarks.lib import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    config = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    limits = {k: v["limit"] for k, v in harness.load_json(
        "limits", cell["name"] + ".json").items()}
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell['chips']}").strip()
        config = harness.merge(config, harness.load_json(
            "configs", "rehearse-overrides.json"))
        traffic = harness.merge(traffic, traffic.get("rehearse", {}))

    try:
        import paddle_tpu
    except ImportError as e:
        print(f"the program is not in this directory: {e}", file=sys.stderr)
        return 3
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"needs a TPU, found {device}", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"cell needs {cell['chips']} chips, found {device}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        paddle_tpu.set_flags({"FLAGS_paged_attn_interpret": True})
    else:
        from paddle_tpu.jit.aot import enable_persistent_compilation_cache
        print(f"[bench] compile cache "
              f"{enable_persistent_compilation_cache()}", flush=True)

    ctx = harness.Context(args, cell, config, traffic, limits, T_START)
    ctx.device_kind = device["kind"]
    jax.monitoring.register_event_listener(ctx.on_compile)
    driver = harness.load_module("drivers", traffic["driver"])
    result = driver.run(ctx)

    e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
    reduction = None
    if ctx.trace and ctx.trace_path:
        from benchmarks.lib import xplane
        try:
            reduction = xplane.Reduction(
                ctx.trace_path, host_spans=traffic.get("host_spans", ()))
            ctx.obs["xplane"] = reduction
        except ValueError:
            if not args.rehearse:   # the CPU's trace has no device plane
                raise
    def in_cell(spec):
        return cell["name"] in spec.get("workloads", [cell["name"]])

    metrics = {}
    if ctx.trace:
        for spec in filter(in_cell, bench["per_layer"]):
            how = harness.load_json("layer_metrics", spec["name"] + ".json")
            reader = harness.load_module("readers", how["reader"])
            value = reader.read(how, ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value,
                                         "unit": spec["unit"]}
    else:
        for spec in filter(in_cell, bench["end_to_end"]):
            value = e2e.get(spec["name"])
            if value is None:
                ctx.check(f"metric {spec['name']} measured", 0, None,
                          at_least=1)
                continue
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    line = result_line(ctx, result, metrics, device, reduction)
    for name, value in sorted(e2e.items()):
        print(f"[bench] {name} {value}", flush=True)
    # what was compared, once more where a refused run's record keeps it:
    # the end of standard error, and `checks` at the end of the line
    for name, c in line["checks"].items():
        print(f"[bench] check {name}: {json.dumps(c, default=float)}",
              file=sys.stderr, flush=True)
    if args.rehearse:
        print("[bench] rehearsal: " + json.dumps(
            {"correct": line["correct"], "metrics": sorted(metrics),
             "checks": line["checks"]}, default=float), flush=True)
        return 0
    print(json.dumps(line, default=float), flush=True)
    return 0


def result_line(ctx, result, metrics, device, reduction=None):
    """The run's last line.  ``correct`` is the conjunction of the run's
    checks, and ``checks``, last on the line, is each of them by the name
    ``ctx.check`` printed: its value, its ``limit`` (or ``at_least``) and
    whether it held, so that a refused run's record says which one."""
    checks = ctx.checked()
    device["memory_peak_bytes"] = ctx.memory_peak
    line = {"correct": all(c["ok"] for c in checks.values()),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s()
        device["window_s"] = reduction.window_s
        line["breakdown"] = {"device_ops": reduction.top_ops(10),
                             "idle_gaps": reduction.idle_gaps(10)}
    line["checks"] = checks
    return line


if __name__ == "__main__":
    sys.exit(main())
