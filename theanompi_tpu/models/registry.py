"""Model registry shared by the CPU sweep and prediction scripts.

One source of truth for the short names used by
``scripts/scaling_sweep.py`` (--model) and ``scripts/predict_scaling.py``:
dotted modelfile, modelclass,
and the synthetic-data config that makes the model runnable with zero data
setup — the same (modelfile, modelclass) import-by-string contract the
reference's launcher used (SURVEY.md §2.1).
"""

MODELS = {
    "alexnet": ("theanompi_tpu.models.alex_net", "AlexNet",
                {"synthetic_batches": 4}),
    "googlenet": ("theanompi_tpu.models.googlenet", "GoogLeNet",
                  {"synthetic_batches": 4}),
    "vgg16": ("theanompi_tpu.models.vggnet_16", "VGGNet_16",
              {"synthetic_batches": 4}),
    "resnet50": ("theanompi_tpu.models.resnet50", "ResNet50",
                 {"synthetic_batches": 4}),
    # sample_kind rides the extra dict: sequence models count sequences,
    # not images
    "transformer_lm": ("theanompi_tpu.models.transformer_lm", "TransformerLM",
                       {"synthetic_train": 2048,
                        "sample_kind": "sequences"}),
    "moe_lm": ("theanompi_tpu.models.transformer_lm", "MoETransformerLM",
               {"synthetic_train": 2048, "sample_kind": "sequences"}),
    # 8192 synthetic samples: enough for a 64-worker × batch-128 global
    # batch in the scaling sweep (the bench's per-chip runs need far less)
    "cifar10": ("theanompi_tpu.models.cifar10", "Cifar10_model",
                {"synthetic_train": 8192}),
}
