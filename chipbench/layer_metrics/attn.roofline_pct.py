"""``flash_attention_roofline`` under the name the issue that defined the
benchmark gave it: the same reader."""

from chipbench.layer_metrics.flash_attention_roofline import read  # noqa: F401
