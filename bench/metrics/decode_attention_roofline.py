"""``kernels/decode_attention.py`` (``_decode_kernel``): share of its
roofline.  Required work per decode row: the query against the lane's valid
K and V prefix (the row's sequence length), not the padded window, and the
output; counted whatever the program does and wherever it keeps them.  The
kernel's calls are the Mosaic custom calls of the rollout program
(``_vrollout``) whose output is one [lanes, heads, head_dim] array (or
whose trace name holds the kernel's name)."""

from bench import trace as tr
from bench.metrics import _count as c


def read(facts):
    rows = c.rows(facts)
    m = facts["model"]

    def is_decode(r):
        a = r["arrays"]
        return "_decode_kernel" in r["text"] or (
            tr.in_module(r, "_vrollout") and r["opcode"] == "custom-call" and len(a) == 1
            and len(a[0][1]) == 3 and a[0][1][1:] == (m["num_heads"], m["head_dim"]))

    k = tr.kernel_seconds(facts["trace"], is_decode) if facts["trace"] else None
    if rows is None or k is None or k[0] <= 0:
        return None
    dec = rows["t"] > 0  # the first step of an episode prefills instead
    flops, byts = c.decode_attention(m, rows["length"][dec])
    return c.roofline_pct(flops, byts, k[0], facts["peak"])
