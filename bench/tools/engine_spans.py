#!/usr/bin/env python3
"""The serving engine's host spans on the chip, at a small size.

    python3 bench/tools/engine_spans.py --out bench/tests/serve_spans.xplane.pb \
        [--seconds 0.25]

Builds the serve cell's engine with qwen3-4b cut to 2 layers at width
128 (as the CPU tests cut it), warms it on a schedule of short requests
due at 16 a second over ``--seconds``, then serves the same schedule
again under the profiler inside ``bench.window`` and writes that
profile, cut to what the readers use (:func:`trim`), to ``--out`` (the
sample of ``bench/tests/test_program_spans.py``).  Prints one JSON line: the
traced run's host phases and counters, its device idle share and the
idle shares of ``bench/harness/program_spans.py``, and what one span
costs on this host with the profiler off and on.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import shutil
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import gen, program_spans, registry, trace  # noqa: E402

SMALL = {"tie_embeddings": True, "n_layers": 2, "d_model": 128,
         "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
         "vocab_size": 512}
TRAFFIC = {"rate_per_s": 16.0,
           "prompt": {"median": 40, "sigma": 0.8, "min": 4, "max": 100},
           "output": {"median": 6, "sigma": 0.7, "min": 2, "max": 12},
           "engine": {"page_size": 16, "n_pages": 64, "decode_slots": 4,
                      "max_context": 128, "prefill_batch": 2,
                      "prefill_chunk": 32}}


def _xplane_pb2():
    """The profile's protobuf module as TensorFlow ships it, loaded by
    path so that TensorFlow itself is not imported."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise SystemExit("engine_spans: cutting a profile needs the XSpace "
                         "protobuf module that TensorFlow ships")
    path = (pathlib.Path(spec.submodule_search_locations[0]) / "tsl"
            / "profiler" / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _kept(plane_name: str, event_name: str) -> bool:
    if plane_name.startswith("/device:TPU:"):
        return True
    return (event_name == trace.WINDOW or event_name == program_spans.COMPILE
            or event_name.startswith(program_spans.SPAN))


def trim(src: pathlib.Path, dst: pathlib.Path) -> None:
    """Write the profile ``src`` cut to what ``trace`` and
    ``program_spans`` read: each TPU's ``XLA Ops`` and ``XLA Modules``
    lines, without the ops' stats, and the host's ``bench.window``,
    ``serve.*`` and compile events, with theirs."""
    pb = _xplane_pb2()
    space, out = pb.XSpace(), pb.XSpace()
    space.ParseFromString(src.read_bytes())
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        events, stats = set(), set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = [e for e in line.events if _kept(
                plane.name, plane.event_metadata[e.metadata_id].name)]
            if not evs:
                continue
            new = kept.lines.add(
                id=line.id, display_id=line.display_id, name=line.name,
                timestamp_ns=line.timestamp_ns, duration_ps=line.duration_ps)
            for e in evs:
                ne = new.events.add()
                ne.CopyFrom(e)
                if device:
                    del ne.stats[:]
                events.add(e.metadata_id)
                for st in ne.stats:
                    stats.add(st.metadata_id)
                    if st.WhichOneof("value") == "ref_value":
                        stats.add(st.ref_value)
        for i in events:
            kept.event_metadata[i].id = i
            kept.event_metadata[i].name = plane.event_metadata[i].name
        for i in stats:
            kept.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    dst.write_bytes(out.SerializeToString())


def span_cost_us(n: int) -> float:
    """Wall time of one empty span with two stats, in microseconds."""
    from repro.serving.metrics import ServeMetrics
    m = ServeMetrics()
    t = time.perf_counter()
    for _ in range(n):
        with m.span("round", queue=1, lanes=2):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True, type=pathlib.Path)
    ap.add_argument("--seconds", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--spans", type=int, default=20000)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("engine_spans: JAX found no TPU")
    from repro.serving import ServeRequest
    from repro.serving.metrics import ServeMetrics

    spec = registry.load_spec()
    config = registry.config(spec, "qwen3-4b")
    config["program_overrides"] = SMALL
    traffic = dict(registry.traffic("serve-chat-steady"), **TRAFFIC)
    cfg, engine = registry.driver("serve").build(config, traffic, args.seed)
    schedule = gen.request_schedule(traffic, args.seconds, args.seed,
                                     cfg.vocab_size)

    def requests():
        return [ServeRequest(rid=str(i), prompt=p, max_new=o, arrival_s=due)
                for i, (due, p, o) in enumerate(schedule)]

    engine.run(requests())                              # warm every shape
    engine.metrics = ServeMetrics()
    work = CHECKOUT / ".bench" / f"engine_spans-{args.out.stem}"
    shutil.rmtree(work, ignore_errors=True)
    with trace.capture(work / "run"):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            m = engine.run(requests())
    path = trace.find_xplane(work / "run")
    args.out.parent.mkdir(parents=True, exist_ok=True)
    trim(path, args.out)

    red = trace.reduce(trace.load(path))
    idle = program_spans.idle_by_event(program_spans.load(path))
    off = span_cost_us(args.spans)
    with trace.capture(work / "cost"):
        on = span_cost_us(args.spans)
    summ = m.summary()
    print(json.dumps({
        "out": str(args.out), "bytes": args.out.stat().st_size,
        "requests": summ["requests"], "decode_steps": m.decode_steps,
        "wall_s": m.wall_s, "host_syncs": m.host_syncs,
        "compiles": m.compiles, "phase_s": m.phase_s, "phase_n": m.phase_n,
        "spans_per_round": sum(m.phase_n.values()) / max(1, m.decode_steps),
        "device_idle_pct": 100.0 * (1.0 - red["busy_s"] / red["window_s"]),
        "idle_shares_pct": program_spans.shares(idle, red["window_s"]),
        "idle_by_event_s": {str(k): v for k, v in idle.items()},
        "span_us_profiler_off": off, "span_us_profiler_on": on}),
        flush=True)
    shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
