"""Bytes of one snapshot of the linear layers' state: the program's gauge
``dynamo_engine_state_bytes{part="slots"}`` over the configuration's slots.
19316736 at Qwen3-Next's widths cut to 9 linear layers (9 x (32 x 128 x 128
float32 + 3 rows x 8192 bfloat16)), beside the 98304 B of keys and values a
page holds: why a snapshot cannot ride every page.  More means a wider state
or one kept in a wider type.  A program without the gauge's part reads
nothing."""

GAUGE = "dynamo_engine_state_bytes"


def read(ctx):
    values = [
        v for (name, labels), v in ctx["counters"].after.items()
        if name == GAUGE and dict(labels).get("part") == "slots"
    ]
    slots = ctx["cfg"].get("engine", {}).get("state_snapshot_slots")
    return values[0] / slots if values and slots else None
