"""Model registry: (model_name, dataset) -> Flax module, mirroring the
reference dispatch (fedml_experiments/distributed/fedavg/main_fedavg.py:354-390
``create_model``) so reference run configs translate 1:1."""

from __future__ import annotations

from typing import Any

from fedml_tpu.models.cnn import CNNDropOut, CNNOriginalFedAvg, LeNet
from fedml_tpu.models.gan import Discriminator, Generator
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.models.mobilenet import MobileNet, MobileNetV3
from fedml_tpu.models.resnet import ResNet18, resnet18_gn, resnet56, resnet110
from fedml_tpu.models.rnn import RNNOriginalFedAvg, RNNStackOverflow
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.models.vgg import VGG


def create_model(model_name: str, output_dim: int, dataset: str = "",
                 dtype: Any = None) -> Any:
    """Reference name/dataset dispatch (main_fedavg.py:354-390). Returns the
    Flax module; task selection (classification/nwp/tag) is the trainer's job
    as in the reference (FedAvgAPI.py:85-91).

    ``dtype`` (jnp dtype or string like "bfloat16") selects the compute
    dtype for models that support one (the CV zoo + TransformerLM); models
    without a dtype field raise a clear error rather than silently ignoring
    the request."""
    model = _create(model_name, output_dim, dataset)
    if dtype is not None and str(dtype) != "float32":
        import dataclasses

        import jax.numpy as jnp

        if isinstance(dtype, str):
            dtype = jnp.dtype(dtype).type
        if not any(f.name == "dtype" for f in dataclasses.fields(model)):
            raise ValueError(
                f"model {model_name!r} does not take a compute dtype"
            )
        model = model.clone(dtype=dtype)
    return model


def _create(model_name: str, output_dim: int, dataset: str = "") -> Any:
    if model_name == "lr" and dataset == "stackoverflow_lr":
        return LogisticRegression(num_classes=output_dim)  # 10004-dim input handled by data
    if model_name == "lr":
        return LogisticRegression(num_classes=output_dim)
    if model_name == "rnn" and dataset == "stackoverflow_nwp":
        return RNNStackOverflow()
    if model_name == "rnn":  # shakespeare / fed_shakespeare
        return RNNOriginalFedAvg()
    if model_name == "cnn":  # femnist
        return CNNDropOut(num_classes=output_dim)
    if model_name == "lenet":  # mobile family (reference torch_lenet.py)
        return LeNet(num_classes=output_dim)
    if model_name == "cnn_original":
        return CNNOriginalFedAvg(num_classes=output_dim)
    if model_name == "resnet18_gn":
        return resnet18_gn(class_num=output_dim)
    if model_name == "resnet56":
        return resnet56(class_num=output_dim)
    if model_name == "resnet110":
        return resnet110(class_num=output_dim)
    if model_name == "mobilenet":
        return MobileNet(num_classes=output_dim)
    if model_name == "mobilenet_v3":
        return MobileNetV3(num_classes=output_dim, mode="large")
    if model_name.startswith("efficientnet"):
        from fedml_tpu.models.efficientnet import efficientnet

        name = model_name if "-" in model_name else "efficientnet-b0"
        return efficientnet(name, num_classes=output_dim)
    if model_name == "unet":
        from fedml_tpu.models.segmentation import UNet

        return UNet(num_classes=output_dim)
    if model_name in ("deeplab", "deeplab_lite"):
        from fedml_tpu.models.segmentation import DeepLabLite

        return DeepLabLite(num_classes=output_dim)
    if model_name == "transformer":
        # long-context LM client (no reference equivalent — extends the zoo
        # past nlp/rnn.py; attn_impl flash/ring for single-/multi-chip)
        return TransformerLM(vocab_size=output_dim)
    if model_name == "moe_transformer":
        # routed experts, grouped KV heads, window and global layers mixed
        # (models/moe_transformer.py); its widths and the share of experts
        # held come from the caller (benchmark/families/moe_lm.py)
        from fedml_tpu.models.moe_transformer import MoETransformerLM

        return MoETransformerLM(vocab_size=output_dim)
    if model_name == "mla_moe_transformer":
        # latent attention, a sigmoid router with a selection bias beside a
        # shared expert, a leading dense layer and a multi-token-prediction
        # module, with a mixer a layer: latent attention, delta attention, a
        # gated short convolution or grouped-query attention with normalised
        # heads, and a head that may be tied (models/mla_moe_transformer.py);
        # widths, mixers and the share held come from the caller
        # (benchmark/families/mla_moe_lm.py, kda_moe_lm.py, conv_moe_lm.py)
        from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM

        return MLAMoETransformerLM(vocab_size=output_dim)
    if model_name.startswith("vgg"):
        depth = int(model_name[3:] or 16)
        return VGG(depth=depth, num_classes=output_dim)
    raise ValueError(f"unknown model {model_name!r} (dataset={dataset!r})")


TASK_BY_DATASET = {
    # reference trainer dispatch (fedml_api/distributed/fedavg/FedAvgAPI.py:85-91)
    "stackoverflow_lr": "tag",
    "stackoverflow_nwp": "nwp",
    "shakespeare": "char_lm",
    "fed_shakespeare": "char_lm",
}


def task_for_dataset(dataset: str) -> str:
    return TASK_BY_DATASET.get(dataset, "classification")
