#!/usr/bin/env python3
"""Record ``data/served.xplane.pb``: a profiler trace of a tiny served run
on the ``device`` engine, with the program's ``ripple.*`` spans.

    python3 bench/tests/record_served_trace.py <out_dir>

Bootstraps a 300-vertex gc-s session behind a one-tenant ``GraphServer``
(micro-batches of 40), warms it on 40 micro-batches, then traces three
more inside a ``bench.window`` span, as the harness does, and writes
``<out_dir>/served.xplane.pb``. Run it on a TPU: the trace's value is its
device plane. The HLO the profiler keeps of each module, and each
operation's HLO text past its name, are dropped from the file: the
reduction reads neither, and they are nine tenths of it.
"""
from __future__ import annotations

import glob
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(out_dir: str) -> int:
    import jax

    from repro.api import InferenceSession, SessionConfig
    from repro.serve import GraphServer

    session = InferenceSession.build(SessionConfig(
        workload="gc-s", engine="device", graph="er", n=300, m=1500,
        d_in=16, d_hidden=16, n_classes=8, seed=0))
    updates = list(session.make_stream(1720, seed=1))
    server = GraphServer(session, tenants=["a"], max_batch=40).start()
    try:
        server.submit("a", updates[:1600])
        server.drain()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            server.submit("a", updates[1600:])
            server.drain()
        jax.profiler.stop_trace()
    finally:
        server.stop()
    path, = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = os.path.join(out_dir, "served.xplane.pb")
    trim(path, out)
    print(out, os.path.getsize(out))
    return 0


def trim(src: str, dst: str) -> None:
    """Copy the trace at ``src`` to ``dst`` without the modules' HLO on the
    metadata plane, and with each event's name cut at ``" = "``: an
    operation keeps its instruction name and a module its whole name, all
    that ``rbench/trace.py`` reads of them. The XPlane schema comes from
    TensorFlow's copy of it."""
    tf = importlib.util.find_spec("tensorflow")
    path = os.path.join(tf.submodule_search_locations[0], "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane)
    space = xplane.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.event_metadata.clear()
        for md in plane.event_metadata.values():
            md.name = md.name.split(" = ")[0]
            md.display_name = ""
            del md.stats[:]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
