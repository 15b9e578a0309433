#!/usr/bin/env python3
"""Writes ``tiny.xplane.pb``: a hand-made trace with known answers, on
which ``run.py --rehearse`` checks ``tracered.reduce_trace``.

One device plane, window 0..1000 us.  "XLA Ops": miller 100..300 us,
while 400..700 us holding ladder 450..550 us and ladder 600..650 us,
hash 900..950 us.  Host spans: bench:window 0..1000 us,
bench:gossip_drain 50..800 us, bench:attestation_batch_verify 90..720 us.

Expected (``EXPECTED`` below): busy 550 us of the 1000 us window (idle
share 45 %); self times miller 200, while 150, ladder 150, hash 50; idle
450 us laid to the innermost host span open at each gap's midpoint.
"""

import os

from jax.profiler import ProfileData

US = 1_000_000  # picoseconds

EXPECTED = {
    "busy_us": 550.0,
    "ops_us": {"miller": 200.0, "while": 150.0, "ladder": 150.0, "hash": 50.0},
    # gaps: 0..100 (mid 50: gossip_drain opens at 50 -> gossip_drain),
    # 300..400 (mid 350: attestation_batch_verify), 700..900 (mid 800:
    # nothing open -> none), 950..1000 (none)
    "gaps_us": {"none": 250.0, "gossip_drain": 100.0, "attestation_batch_verify": 100.0},
    "modules_us": {"jit_chain": 600.0},
}


def event(meta: int, start_us: float, dur_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)} }}")


def metadata(names: dict[int, str]) -> str:
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())


TEXT = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {event(10, 100, 600)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {event(1, 100, 200)} {event(2, 400, 300)} {event(3, 450, 100)}
    {event(3, 600, 50)} {event(4, 900, 50)} }}
  {metadata({1: "miller", 2: "while", 3: "ladder", 4: "hash", 10: "jit_chain"})}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "python" timestamp_ns: 0
    {event(1, 0, 1000)} {event(2, 50, 750)} {event(3, 90, 630)} {event(4, 10, 5)} }}
  {metadata({1: "bench:window", 2: "bench:gossip_drain",
             3: "bench:attestation_batch_verify", 4: "some_other_traceme"})}
}}
"""

if __name__ == "__main__":
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny.xplane.pb")
    with open(out, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(out, os.path.getsize(out), "bytes")
