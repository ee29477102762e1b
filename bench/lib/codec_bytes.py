"""The least HBM traffic of a step's block-codec work, counted from shapes.

For every compressed collective the program's ledger recorded at compile
time, and for the optimizer's 8-bit state, count the bytes an encode, a
decode or a fused ring hop must read and write, however it is
implemented:

    encode         read the value (its dtype), write the wire
    decode         read the wire, write the value
    fused hop      read the wire and the local value, write the wire
                   (the last hop of a reduction writes the f32 sum instead)

The wire of a ``bq<b>`` codec is b/8 bytes a value plus a 4-byte scale per
block of 128.  A ring reduce-scatter over n ranks of E values moves E/n a
hop; an all-gather encodes its own shard once and decodes the n - 1 it
receives; an all-reduce is the two.  Backward twins are counted on their
transposed payload, a rematerialised forward twice, and each event as many
times as its layer repeats.  Codecs that are not block codecs are not
counted.
"""

from __future__ import annotations

import re

_ITEM = {"float32": 4, "bfloat16": 2, "float16": 2}
_BQ = re.compile(r"^(?:ef:)?bq(\d+)$")


def wire_per_value(bits: int) -> float:
    return bits / 8 + 4 / 128


def _bits(codec: str):
    m = _BQ.match(codec or "")
    return int(m.group(1)) if m else None


def collective(op: str, bits: int, elems: float, n: int, item: int) -> float:
    """Bytes of one execution of a compressed collective on one device."""
    if n <= 1:
        return 0.0
    w = wire_per_value(bits)
    if op == "ppermute":
        return elems * (item + w) + elems * (w + item)
    if op == "all_gather":
        return elems * (item + w) + (n - 1) * elems * (w + item)
    c = elems / n
    rs = c * (item + w) + (n - 2) * c * (w + item + w) + c * (w + item + 4)
    if op == "reduce_scatter":
        return rs
    if op == "all_reduce":
        return rs + c * (4 + w) + (n - 1) * c * (w + item)
    return 0.0


def event(ev: dict) -> float:
    """Codec bytes of one ledger event, forward and backward."""
    n, item = ev["n"], _ITEM.get(ev["dtype"], 4)
    total = 0.0
    b = _bits(ev["codec_fwd"])
    if b:
        total += collective(ev["op"], b, ev["elems"], n, item) \
            * (2 if ev.get("remat") else 1)
    b = _bits(ev["codec_bwd"])
    if b and ev.get("bwd_op"):
        op_b = ev["bwd_op"]
        if ev["op"] == "all_gather" and op_b == "reduce_scatter":
            e_b = ev["elems"] * n
        elif ev["op"] == "reduce_scatter" and op_b == "all_gather":
            e_b = -(-ev["elems"] // n)
        else:
            e_b = ev["elems"]
        total += collective(op_b, b, e_b, n, item)
    return total * ev["mult"]


def optimizer_state(state_bits: int, flat_len: int) -> float:
    """8-bit Adam state: decode m and sqrt(v), encode both again."""
    if state_bits != 8:
        return 0.0
    return 4 * flat_len * (4 + wire_per_value(8))


def per_step(events, state_bits: int, flat_len: int) -> float:
    return sum(event(ev) for ev in events) \
        + optimizer_state(state_bits, flat_len)
