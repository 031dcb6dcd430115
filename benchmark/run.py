#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The process that runs this holds the chip: it is the server (and, for an
``in_process`` configuration, the client too), warms every shape the
cell's traffic can form, opens a window of ``--seconds`` seconds and
prints, as the last line of its standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and,
traced, ``breakdown``. Everything else goes on earlier lines. It exits
with a code other than 0, and prints no result, unless JAX finds a TPU
with the chips the cell asks for.

``--rehearse-on-cpu`` walks the same control flow on the CPU at tiny
sizes. It proves nothing about the chip and prints no metric.
``--control <name>`` breaks a guarantee of the configuration underneath
the timed path; ``correct`` then has to come out false.
"""

import time

T_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

REHEARSAL_MAX_BYTES = 4096
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(text: str) -> None:
    print(text, flush=True)


def mark(marks: list, what: str) -> None:
    marks.append((what, (time.monotonic_ns() - T_START_NS) / 1e9))


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    p.add_argument("--control", default=None)
    p.add_argument("--records-out", default=None,
                   help="also save the per-call records (.npy) here")
    p.add_argument("--keep-trace", default=None,
                   help="also copy the profiler's .xplane.pb here")
    return p.parse_args(argv)


def start_generator(job: dict) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "generator.py"),
         "--job", json.dumps(job)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
    )


def expect(child: subprocess.Popen, word: str) -> str:
    line = child.stdout.readline().strip()
    if not line.startswith(word):
        raise RuntimeError(f"generator said {line!r}, not {word}")
    return line


def open_cell(args):
    """The cell with its traffic as this run sends it; the allocator
    policy is applied before anything allocates in earnest."""
    from benchmark import allocator, manifest

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    allocator.apply(cell.config["allocator"])
    traffic = dict(cell.traffic)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell.chips}"
        )
        traffic["sizes"] = [min(s, REHEARSAL_MAX_BYTES) for s in traffic["sizes"]]
        traffic["warm_seconds"] = min(traffic["warm_seconds"], 0.2)
    else:
        from incubator_brpc_tpu.utils import compile_cache

        say(f"compile cache: {compile_cache.configure()}")
    return cell, traffic


def find_device(cell, rehearse: bool):
    """What JAX found, and the peaks of that kind; ``None`` where the
    cell cannot be measured here."""
    import jax

    from benchmark import manifest

    found = jax.devices()
    device = {
        "platform": found[0].platform,
        "kind": found[0].device_kind,
        "count": len(found),
    }
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} workload={cell.name}")
    peaks = manifest.load_json("peaks.json")["device_kinds"].get(device["kind"])
    if not rehearse:
        if device["platform"] != "tpu" or device["count"] < cell.chips:
            say(f"REFUSED: {cell.name} needs {cell.chips} TPU chip(s)")
            return None
        if peaks is None:
            say(f"REFUSED: no peaks recorded for {device['kind']!r}")
            return None
    return device, peaks


def drive(args, cell, traffic, scratch, marks) -> dict:
    """Build the deployment, warm it, open the window and wait it out.
    Returns everything the window left behind."""
    import jax
    import numpy as np

    from benchmark import generator, spans, xplane

    config, traced = cell.config, bool(args.trace)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: name == COMPILE_EVENT
        and compiles.append(time.monotonic_ns())
    )
    handler_spans = spans.HandlerSpans() if traced else None
    deployment = cell.deployment().Deployment(config, args.control, handler_spans)
    own_process = config["generator"] == "own_process"
    child, out = None, {"compiles": compiles, "sync_ns": None}
    try:
        if own_process:
            records_path = os.path.join(scratch, "records.npy")
            child = start_generator({
                "root": ROOT, "port": deployment.port, "traffic": traffic,
                "reference": config["reference"], "seed": args.seed,
                "seconds": args.seconds, "out": records_path,
                "channel_options": config["channel_options"],
                "allocator": config["allocator"],
            })
        deployment.warm(traffic)
        mark(marks, "shapes warm")
        if own_process:
            expect(child, "READY")
            mark(marks, "generator ready")
        else:
            send = generator.channel_caller(
                deployment.channel(), traffic, cell.reference())
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(
                os.path.join(scratch, "trace"), profiler_options=options)
            out["sync_ns"] = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(xplane.SYNC_MARK):
                pass
            mark(marks, "profiler started")
        before = {}

        def opened(_ns):
            before.update(spans.counters())

        if own_process:
            child.stdin.write("GO\n")
            child.stdin.flush()
            t_open = int(expect(child, "OPEN").split()[1])
            opened(t_open)
            time.sleep(max(0.0, args.seconds - (time.monotonic_ns() - t_open) / 1e9))
            after = spans.counters()
            expect(child, "DONE")
            child.wait(timeout=60)
            table = np.load(records_path)
        else:
            table, t_open = generator.run_load(
                send, traffic, args.seed, args.seconds, on_open=opened
            )
            after = spans.counters()
        if traced:
            jax.profiler.stop_trace()
            rows = np.asarray(handler_spans.rows, np.int64).reshape(-1, 2)
            out["handler"] = rows
        out.update(
            table=table, t_open=t_open,
            t_close=t_open + int(args.seconds * 1e9),
            counters=spans.delta(before, after), held=deployment.holds(),
            peak_bytes=max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in deployment.devices
            ),
        )
        return out
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        deployment.close()


def judge(args, traffic, window: dict, marks) -> tuple:
    """Print each number compared beside its limit, and what else a
    reader of the log needs. Returns ``(correct, failed, compilations)``."""
    from benchmark import generator, reduce

    table, t_open, t_close = window["table"], window["t_open"], window["t_close"]
    rpc_failed = int((table[:, generator.STATUS] == generator.RPC_FAILED).sum())
    mismatched = int((table[:, generator.STATUS] == generator.MISMATCH).sum())
    done = len(reduce.in_window(table, t_open, args.seconds))
    checks = [
        ("responses_not_equal_to_their_request", mismatched, 0, mismatched == 0),
        ("calls_failed", rpc_failed, 0, rpc_failed == 0),
        ("correct_calls_in_window", done, ">=1", done >= 1),
    ] + window["held"]
    for what, value, limit, ok in checks:
        say(f"CHECK {what}: {value} (limit {limit}) {'ok' if ok else 'NOT HELD'}")
    compilations = sum(t_open <= t <= t_close for t in window["compiles"])
    say("set-up, seconds from the start: "
        + ", ".join(f"{what} {at:.2f}" for what, at in marks))
    say(f"compilations inside the window: {compilations} "
        f"(of {len(window['compiles'])} in the process)")
    say(f"longest time without a completion: "
        f"{reduce.longest_silence_s(table, t_open, args.seconds):.3f} s")
    say(f"completions per second: {reduce.per_second(table, t_open, args.seconds)}")
    if traffic["arrival"] == "open":
        say(f"generator lateness, median: {reduce.lateness_us(table):.1f} us")
    correct = all(ok for *_rest, ok in checks)
    return correct, rpc_failed + mismatched, compilations


def traced_metrics(args, cell, traffic, window: dict, scratch, device, peaks):
    """The cell's per-layer metrics and the breakdown, from the trace, the
    handler spans and the counters. Adds ``busy_s`` and ``window_s`` to
    ``device``."""
    import numpy as np

    from benchmark import generator, reduce, xplane

    table, t_open, t_close = window["table"], window["t_open"], window["t_close"]
    path = xplane.find_trace(os.path.join(scratch, "trace"))
    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        shutil.copy(path, args.keep_trace)
    trace = xplane.read_trace(path)
    if trace.sync_ns is None:
        raise RuntimeError("the trace lacks the sync mark: no common clock")
    trace = trace.shifted(window["sync_ns"] - trace.sync_ns)
    used = {p: lines for p, lines in trace.devices.items()
            if len(lines["ops"].clip(t_open, t_close))}
    say(f"trace: device planes {sorted(trace.devices)}, with work in the "
        f"window {sorted(used)}")
    handler = window["handler"]
    handler = handler[(handler[:, 0] >= t_open) & (handler[:, 1] <= t_close)]
    # what a per-layer reader may read (benchmark/README.md lists it)
    run = types.SimpleNamespace(
        cell=cell, traffic=traffic, seconds=args.seconds, t_open=t_open,
        t_close=t_close, table=table,
        done=reduce.in_window(table, t_open, args.seconds), handler=handler,
        counters=window["counters"], devices=used, peaks=peaks,
        window_s=args.seconds, busy_s=None,
    )
    breakdown = None
    if used:
        per_device = [xplane.busy(d["ops"], t_open, t_close) for d in used.values()]
        run.busy_s = float(np.mean([b for b, _gaps in per_device]))
        device["busy_s"], device["window_s"] = run.busy_s, run.window_s
        busiest = max(per_device, key=lambda bg: bg[0])[1]
        breakdown = {
            "device_ops": xplane.top_ops(
                [d["ops"].clip(t_open, t_close) for d in used.values()]),
            "idle_gaps": xplane.label_gaps(
                busiest, (handler[:, 0], handler[:, 1]),
                (table[:, generator.SEND_NS], table[:, generator.END_NS])),
        }
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, breakdown


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import reduce

    cell, traffic = open_cell(args)
    marks = []
    import jax  # noqa: F401 — timed: importing it is part of set-up

    mark(marks, "jax imported")
    found = find_device(cell, args.rehearse_on_cpu)
    if found is None:
        return 2
    device, peaks = found
    mark(marks, "devices found")
    controls = cell.deployment().CONTROLS
    if args.control is not None and args.control not in controls:
        raise SystemExit(f"--control is one of {controls}")

    scratch = tempfile.mkdtemp(prefix="benchmark_")
    try:
        window = drive(args, cell, traffic, scratch, marks)
        table, t_open = window["table"], window["t_open"]
        setup_s = (t_open - T_START_NS) / 1e9
        marks.append(("window open", setup_s))
        correct, failed, compilations = judge(args, traffic, window, marks)
        if args.records_out:
            import numpy as np

            os.makedirs(os.path.dirname(os.path.abspath(args.records_out)),
                        exist_ok=True)
            np.save(args.records_out, np.concatenate(
                ([[0, t_open, t_open, window["t_close"], 0, -1]], table)))
        breakdown = None
        if args.trace:
            metrics, breakdown = traced_metrics(
                args, cell, traffic, window, scratch, device, peaks)
        else:
            values = reduce.end_to_end(table, t_open, args.seconds)
            values["setup_s"] = setup_s
            metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end if m["name"] in values
            }
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    device["memory_peak_bytes"] = int(window["peak_bytes"])
    result = {
        "correct": bool(correct), "attempted": len(table), "failed": failed,
        "metrics": metrics, "device": device,
        "compilations_in_window": compilations,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse_on_cpu:
        say("REHEARSAL on the CPU: control flow only, no metric is reported")
        result.update(metrics={}, rehearsal=True)
        result.pop("breakdown", None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if code != e.code:
            print(e.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 — report, then leave without a result
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    os._exit(code)  # the program's daemon reactors and pools never join
