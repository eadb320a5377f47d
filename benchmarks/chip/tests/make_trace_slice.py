"""Cut a short slice out of a chip trace, for ``testdata/``.

  python3 benchmarks/chip/tests/make_trace_slice.py <trace.xplane.pb.gz> \
      <out.xspace.gz> <start_s> <length_s>

Keeps the device planes' ``XLA Modules`` and ``XLA Ops`` events and the
host's ``bench.*`` spans that start inside [start, start + length) after
the trace's ``bench.window`` opened, and makes that slice the new
``bench.window``.  Written as a serialized XSpace, gzipped.
"""

import gzip
import sys

import _paths  # noqa: F401

from jax.profiler import ProfileData

from benchmarks.chip import xtrace

KEEP_LINES = ("XLA Modules", "XLA Ops")


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def slice_text(pd, start_s: float, length_s: float) -> str:
    window = next(e for p in pd.planes if p.name.startswith("/host:")
                  for ln in p.lines for e in ln.events
                  if e.name == xtrace.WINDOW)
    a = window.start_ns + start_s * 1e9
    b = a + length_s * 1e9
    out, pid = [], 0
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not host and not plane.name.startswith("/device:TPU:"):
            continue
        names: dict[str, int] = {}
        lines = []
        for ln in plane.lines:
            if not host and ln.name not in KEEP_LINES:
                continue
            evs = [e for e in ln.events if a <= e.start_ns < b
                   and (not host or (e.name.startswith("bench.")
                                     and e.name != xtrace.WINDOW))]
            if host and ln.name == "python3":
                evs.append(None)                  # the new window
            if not evs:
                continue
            body = []
            for e in evs:
                name, t0, dur = ((xtrace.WINDOW, a, b - a) if e is None
                                 else (e.name, e.start_ns, e.duration_ns))
                mid = names.setdefault(name, len(names) + 1)
                body.append(f"events {{ metadata_id: {mid} offset_ps: "
                            f"{int(round((t0 - a) * 1000))} duration_ps: "
                            f"{int(round(dur * 1000))} }}")
            lines.append(f"lines {{ id: {len(lines) + 1} name: {_q(ln.name)}"
                         f" timestamp_ns: {int(a)} " + " ".join(body) + " }")
        if not lines:
            continue
        pid += 1
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_q(n)} }} }}" for n, i in names.items())
        out.append(f"planes {{ id: {pid} name: {_q(plane.name)} "
                   + " ".join(lines) + " " + meta + " }")
    return "\n".join(out)


def main() -> None:
    src, dst, start, length = sys.argv[1:5]
    text = slice_text(xtrace.load(src), float(start), float(length))
    with gzip.open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


if __name__ == "__main__":
    main()
