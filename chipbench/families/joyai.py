"""``joyai``: ``models/xing.py``'s latent-attention expert block on ONE
plain residual stream (a file without ``hc_mult``), at the sizes of a
``joyai_llm_flash`` ``config.json`` (source key names) plus the keys the
source lacks (the configuration's ``assumed``), trained on the next-token
loss plus the multi-token-prediction module's: ``num_nextn_predict_layers``
is the source's, the module is held. The program, the chip's share, the
count of the uncut model and the ``shape`` are ``families/xing.py``'s
(``streams`` 0: ``flops_xing`` then counts no hyper-connection term); the
plain reference is this family's own, ``reference/joyai.py``."""

import types

from pytorch_ps_mpi_tpu.models.xing import XingConfig

from chipbench.families import xing as xing_family
from chipbench.reference import joyai as reference

# a program whose block knows only the hyper-connected path (and rotates
# halves whatever the source says) cannot run this family: it fails here,
# at the job's import of the family, before the chip is attached
if "rope_interleave" not in XingConfig.__dataclass_fields__:
    raise ImportError("pytorch_ps_mpi_tpu.models.xing has no plain residual "
                      "path and no interleaved rotary pairing: the joyai "
                      "family needs both")


def build(config: dict, traffic: dict):
    if "hc_mult" in config:
        raise ValueError("a joyai file has no hc_mult: one plain residual "
                         "stream (the hyper-connected block is the xing "
                         "family's)")
    fam = xing_family.build(config, traffic)
    fam.reference = types.SimpleNamespace(
        terms=xing_family.before_its_trace(reference.terms),
        router_loads=xing_family.before_its_trace(reference.router_loads))
    return fam
