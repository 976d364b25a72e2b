"""Record bench/traces/scopes.xplane.pb: a small TPU trace of the program
with its named scopes and host spans, for bench/tests/test_xplane_scopes.py.

    python3 bench/tests/record_scopes.py [out.xplane.pb]

It needs one TPU.  Each cell's entry is built at a tiny size (dense: one
configuration, 16 slots; fleet: 1,008 servers, one 16-slot chunk with two
routing rounds and 8 water-fill iterations), warmed up, then called once
inside a `bench.call` span with the profiler on (host tracer level 1: the
annotations of the program and the harness, not JAX's own), and the file is
cut to what the readers use (`shrink`) to keep it under 1 MB.  It prints
what each call recorded.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

CELLS = ("paper24.bp_fig3", "paper24.mw_fig3", "borg10k.bp_uniform")


def tiny(config: dict, workload: dict):
    config, workload = dict(config), json.loads(json.dumps(workload))
    if workload["entry"] == "sweep":
        config.update(horizon=16, warmup=4)
        workload.update(loads=workload["loads"][-1:],
                        errors=workload["errors"][:1], seeds_per_call=1)
    else:
        config.update(num_servers=1008, horizon=16, warmup=4, fill_iters=8)
    return config, workload


def shrink(path: Path) -> None:
    """Cut the trace to what the readers use, in place: the fields that
    bench/xplane_scopes.py declares, each operation's name to its short
    form (`%fusion.12`) and its stats to `tf_op`, the device planes to their
    "XLA Ops" and "XLA Modules" lines, the host plane to the harness's and
    the program's spans (`bench.*`, `sim.*`), and the metadata to what the
    kept events use."""
    import xplane_scopes
    from trace_reduce import MODULES, OPS

    space = xplane_scopes._space_class()()
    space.ParseFromString(path.read_bytes())
    space.DiscardUnknownFields()
    for plane in space.planes:
        tf_op = {e.key for e in plane.stat_metadata if e.value.name == "tf_op"}
        refs = set()
        for entry in plane.event_metadata:
            meta = entry.value
            meta.name = meta.name.split(" = ", 1)[0]
            kept = [st for st in meta.stats if st.metadata_id in tf_op]
            del meta.stats[:]
            meta.stats.extend(kept)
            refs.update(st.ref_value for st in kept)
        kept = [e for e in plane.stat_metadata if e.key in tf_op | refs]
        del plane.stat_metadata[:]
        plane.stat_metadata.extend(kept)
        if plane.name.startswith("/device:TPU:"):
            keep = [line for line in plane.lines if line.name in (OPS, MODULES)]
        elif plane.name == "/host:CPU":
            ours = {e.key for e in plane.event_metadata
                    if e.value.name.startswith(("bench.", "sim."))}
            for line in plane.lines:
                kept = [e for e in line.events if e.metadata_id in ours]
                del line.events[:]
                line.events.extend(kept)
            keep = [line for line in plane.lines if len(line.events)]
        else:
            keep = []
        del plane.lines[:]
        plane.lines.extend(keep)
        used = {e.metadata_id for line in keep for e in line.events}
        kept = [e for e in plane.event_metadata if e.key in used]
        del plane.event_metadata[:]
        plane.event_metadata.extend(kept)
    path.write_bytes(space.SerializeToString())


def main(out: Path) -> int:
    import jax
    from repro.sharding import sim as fleet_sim
    import scope_report
    import xplane_scopes
    from trace_reduce import Trace

    devices = run.find_chips(jax, 1)
    if devices is None:
        return 3
    spec = run.load_json(BENCH.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    entries = []
    for name in CELLS:
        workload = run.load_json(BENCH / "workloads" / f"{name}.json")
        config = run.load_json(BENCH / "configs" / f"{cells[name]['config']}.json")
        config, workload = tiny(config, workload)
        entry = run.load_module("entries", workload["entry"]).Entry(
            config, workload, 20261017, devices[0])
        if workload["entry"] == "simulate":
            entry.fc = fleet_sim.FleetConfig(chunk=16, rounds=2, fill_iters=8)
        entry.warmup()
        entries.append((name, entry))

    tmp = BENCH / "out" / "record"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # the program's spans, not JAX's own
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        for i, (_, entry) in enumerate(entries):
            with jax.profiler.TraceAnnotation("bench.call", call=i):
                entry.call(i)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    shrink(out)

    trace, scopes = Trace.from_file(out), xplane_scopes.read(out)
    calls = sorted((s, e) for n, s, e, _ in trace.host if n == "bench.call")
    for (name, entry), (lo, hi) in zip(entries, calls):
        one = Trace(trace.devices, [h for h in trace.host
                                    if lo <= h[1] and h[2] <= hi])
        got = scope_report.report(one, scopes, entry.facts()["backend"],
                                  slots=1, calls=1)
        print(f"{name}: {json.dumps(got)}", flush=True)
    print(f"wrote {out} ({out.stat().st_size} bytes)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1
                  else BENCH / "traces" / "scopes.xplane.pb"))
