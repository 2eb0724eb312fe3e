"""``resnet``: the async job's problem. ``async_train.make_problem``
builds model, parameters and batches itself from a job ``cfg``, in the
server and in every worker alike, so this family only says which ``cfg``
stands for the configuration file — and refuses a file whose sizes the
program cannot build."""

import types

BUILDABLE = {"stage_sizes": [2, 2, 2, 2], "num_filters": 64,
             "block": "basic", "stem": "conv3x3", "norm": "group",
             "dtype": "float32"}


def build(config: dict, traffic: dict):
    for key, want in BUILDABLE.items():
        if config[key] != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r}, but "
                f"async_train.make_problem builds only {want!r}")
    return types.SimpleNamespace(
        unit="samples", units_per_row=1,
        stage_sizes=tuple(config["stage_sizes"]),
        problem_cfg={"model": "resnet18",
                     "model_kw": {"num_classes": config["num_classes"]},
                     "in_shape": list(config["image_shape"])})
