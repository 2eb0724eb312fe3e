"""``gpt_lm``: ``models/gpt.py::GPTLM`` (tied head) at the sizes of a
GPT-2 ``config.json``, trained on the next-token loss."""

from chipbench.families.transformer import family
from chipbench.reference.transformer import lm_terms


def build(config: dict, traffic: dict):
    from pytorch_ps_mpi_tpu.models.gpt import GPTLM, causal_lm_loss

    def loss(model, params, batch):
        return causal_lm_loss(model.apply(params, batch["tokens"]),
                              batch["tokens"])

    return family(
        model_cls=GPTLM, loss=loss, terms=lm_terms, causal=True,
        config=config, traffic=traffic,
        sizes=dict(vocab=config["vocab_size"], hidden=config["n_embd"],
                   layers=config["n_layer"], heads=config["n_head"],
                   ffn=config["n_inner"], max_position=config["n_positions"]))
