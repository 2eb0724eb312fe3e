"""Device time a step under the prediction module's own scopes: every
scope of the model behind ``mtp.`` (``mtp.block``: the two norms, the
joining product ``[h ; e] W_eh`` and the embedding's second read;
``mtp.attn.mla_proj``, ``mtp.attn.mla`` with its flash kernels,
``mtp.moe.shared``) and ``loss.mtp`` (the module's norm, the head's second
product and its cross-entropy), forward, again under ``remat``, and
backward, on the first device. The module's ROUTED part is NOT here: its
router, moves and grouped products stay under ``moe.route|dispatch|
experts|combine`` (one of the cell's five expert layers), since
``parallel/dropless.py``'s scopes keep their names inside the module and
XLA's own instructions (the ragged products, the sorts) lose the path.
Absent where no such scope ran or the job gives no scope table."""

from chipbench.scope_time import seconds_per_step

SCOPES = r"^mtp\.|^loss\.mtp$"


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, SCOPES)
    return None if per_step is None else 1e3 * per_step
