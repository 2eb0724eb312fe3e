"""Device time a step under latent attention's projections (``attn.mla_proj``,
and the prediction module's behind ``mtp.``: the five products ``W_qa``,
``W_qb``, ``W_kva``, ``W_kvb`` and ``W_o`` with the two latent norms, the
rotary embedding and the assembly of the 192-wide keys, in every layer;
forward, again under ``remat``, and backward with each matrix's
weight-gradient product), on the first device. Absent where no such scope
ran or the job gives no scope table."""

from chipbench.scope_time import seconds_per_step

SCOPES = r"^(mtp\.)?attn\.mla_proj$"


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, SCOPES)
    return None if per_step is None else 1e3 * per_step
