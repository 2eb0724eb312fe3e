"""``bert_mlm``: ``models/bert.py::BertMLM`` at the sizes of a
google-research ``bert_config.json``, trained on the masked-LM loss."""

from chipbench.families.transformer import family
from chipbench.reference.transformer import mlm_terms


def build(config: dict, traffic: dict):
    from pytorch_ps_mpi_tpu.models.bert import BertMLM, mlm_loss

    def loss(model, params, batch):
        return mlm_loss(model.apply(params, batch["tokens"]),
                        batch["targets"], batch["mask"])

    return family(
        model_cls=BertMLM, loss=loss, terms=mlm_terms, causal=False,
        config=config, traffic=traffic,
        sizes=dict(vocab=config["vocab_size"], hidden=config["hidden_size"],
                   layers=config["num_hidden_layers"],
                   heads=config["num_attention_heads"],
                   ffn=config["intermediate_size"],
                   max_position=config["max_position_embeddings"]))
