"""``kernels/flash_attention.py`` forward (``_flash_kernel``): share of its
roofline.  Its calls in a PPO-LM window: the prefill of each episode's
prompt, the value bootstrap over every sampled row's successor, and the
learner's forward of every trained row in each epoch.  Required work per
call is causal attention over each row's valid length, not over ``ctx``.
The kernel's calls are the Mosaic custom calls of those three programs
(``_vrollout``, ``_postprocess_cols``, ``_learn``) whose output is one
[rows, heads, positions, head_dim] array (or whose trace name holds its
name)."""

from bench import trace as tr
from bench.metrics import _count as c


def read(facts):
    rows = c.rows(facts)
    m = facts["model"]

    def is_flash(r):
        a = r["arrays"]
        return "_flash_kernel" in r["text"] or (
            any(tr.in_module(r, fn) for fn in ("_vrollout", "_postprocess_cols", "_learn"))
            and r["opcode"] == "custom-call" and len(a) == 1 and len(a[0][1]) == 4
            and a[0][1][1] == m["num_heads"] and a[0][1][3] == m["head_dim"])

    k = tr.kernel_seconds(facts["trace"], is_flash) if facts["trace"] else None
    if rows is None or k is None or k[0] <= 0:
        return None
    L = rows["length"]
    epochs = facts["traffic"]["sgd_epochs"]
    f1, b1 = c.causal_attention(m, L)           # learner, per epoch
    f2, b2 = c.causal_attention(m, L + 1)       # bootstrap over successors
    f3, b3 = c.causal_attention(m, L[rows["t"] == 0])  # prompt prefill
    return c.roofline_pct(epochs * f1 + f2 + f3, epochs * b1 + b2 + b3, k[0], facts["peak"])
