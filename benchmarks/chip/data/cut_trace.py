#!/usr/bin/env python3
"""Cut a traced run's ``.xplane.pb`` down to one prefill and the three decode
steps after it: the device's module, operation and async lines and the
benchmark's host spans, with the traced-window span narrowed to the cut.
It made ``trace_small.xplane.pb`` from a ``--trace 1`` run on a TPU v5e.

  python3 benchmarks/chip/data/cut_trace.py <in.xplane.pb> <out.xplane.pb>

Needs the XPlane protobuf module that ships with TensorFlow.
"""
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

WINDOW = "bench.traced_window"
HOST_SPANS = ("engine.", "spec.", "hyp.", "client.", "bench.")
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")


def _ns(line, ev):
    return line.timestamp_ns + ev.offset_ps / 1000.0


def cut_window(dev):
    """From 0.2 ms before a prefill to 0.5 ms after the third decode step."""
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    seq = sorted((_ns(mods, e), _ns(mods, e) + e.duration_ps / 1000.0,
                  dev.event_metadata[e.metadata_id].name) for e in mods.events)
    for i, (t0, _, name) in enumerate(seq):
        if not name.startswith("jit__prefill_one"):
            continue
        decodes = [s for s in seq[i + 1:] if s[2].startswith("jit__decode")][:3]
        if len(decodes) == 3:
            return t0 - 2e5, decodes[-1][1] + 5e5
    raise ValueError("no prefill followed by three decode steps")


def main(src: str, dst: str) -> None:
    xs = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        xs.ParseFromString(f.read())
    dev = next(p for p in xs.planes if p.name == "/device:TPU:0")
    start, end = cut_window(dev)
    out = xplane_pb2.XSpace()
    for p in xs.planes:
        if p.name not in ("/device:TPU:0", "/host:CPU"):
            continue
        q = out.planes.add()
        q.id, q.name = p.id, p.name
        used, used_stats = set(), set()
        for ln in p.lines:
            if p.name == "/device:TPU:0" and ln.name not in DEVICE_LINES:
                continue
            keep = []
            for e in ln.events:
                name = p.event_metadata[e.metadata_id].name
                t0 = _ns(ln, e)
                if p.name == "/host:CPU" and not name.startswith(HOST_SPANS):
                    continue
                if name != WINDOW and (t0 + e.duration_ps / 1000.0 < start or t0 > end):
                    continue
                keep.append((e, name))
            if not keep:
                continue
            m = q.lines.add()
            m.id, m.display_id, m.name = ln.id, ln.display_id, ln.name
            m.display_name, m.timestamp_ns, m.duration_ps = (
                ln.display_name, ln.timestamp_ns, ln.duration_ps)
            for e, name in keep:
                n = m.events.add()
                n.CopyFrom(e)
                if name == WINDOW:
                    n.offset_ps = int((start - ln.timestamp_ns) * 1000)
                    n.duration_ps = int((end - start) * 1000)
                used.add(e.metadata_id)
                used_stats.update(s.metadata_id for s in n.stats)
        for k in used:
            q.event_metadata[k].CopyFrom(p.event_metadata[k])
        for k in used_stats:
            q.stat_metadata[k].CopyFrom(p.stat_metadata[k])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
