"""Model zoo for the BASELINE configs.

The reference ships no models (SURVEY "What the reference is NOT") — its
train scripts lived in a sibling research repo — but the BASELINE configs
(BASELINE.json) name the families the framework must drive: a 2-layer MLP
(MNIST), ResNet-18/50 (CIFAR-10 / ImageNet), and BERT-base MLM. All are
flax modules designed TPU-first: stateless norms in the grad path,
bfloat16-friendly, static shapes, ring-attention option for long context.

The config-driven decoders are plain functions over a parameter pytree,
one module a family, each read from its source ``config.json``'s key names
(``<module>.<Config>.from_source``, ``init``, ``apply``, a loss,
``router_loads``): ``sdar_moe`` (SDAR-30B-A3B-Chat), ``sambay``
(Phi-4-mini-flash-reasoning), ``xing`` (Xing4.0-29B-A4B, and on one plain
residual stream JoyAI-LLM-Flash) and ``lfm2`` (LFM2-24B-A2B; its pair
decoder also runs ``solar``, Solar-Open2-250B, whose module holds the
configuration and ``init`` only). They are imported by module, ``from
pytorch_ps_mpi_tpu.models import lfm2``, and only ``lfm2``'s config class
is exported by name beside the flax modules'.
"""

from pytorch_ps_mpi_tpu.models.mlp import MLP
from pytorch_ps_mpi_tpu.models.resnet import ResNet, ResNet18, ResNet50
from pytorch_ps_mpi_tpu.models.bert import BertConfig, BertMLM, stack_layer_params
from pytorch_ps_mpi_tpu.models.moe import SwitchConfig, SwitchMLM
from pytorch_ps_mpi_tpu.models.gpt import GPTLM, causal_lm_loss, gpt_config, gpt_tiny
from pytorch_ps_mpi_tpu.models.lfm2 import Lfm2Config

__all__ = ["MLP", "ResNet", "ResNet18", "ResNet50", "BertConfig", "BertMLM",
           "SwitchConfig", "SwitchMLM", "GPTLM", "causal_lm_loss",
           "gpt_config", "gpt_tiny", "Lfm2Config"]
