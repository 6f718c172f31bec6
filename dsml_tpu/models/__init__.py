"""Model families: MLP (MNIST), CNN, ResNet-18 (CIFAR-10), GPT-2, Llama, Jamba, Mellum, DeepSeek-V3."""

from dsml_tpu.models.mlp import MLP  # noqa: F401


def model_by_family(family: str, name: str, **tiny_kwargs):
    """(model, config) for a family + preset — the ONE dispatch point the
    CLI examples share (``--family gpt2|llama``). ``tiny_kwargs`` reach only
    the ``tiny`` preset (each family's ``by_name`` enforces that)."""
    if family == "llama":
        from dsml_tpu.models.llama import Llama, LlamaConfig

        cfg = LlamaConfig.by_name(name, **tiny_kwargs)
        return Llama(cfg), cfg
    if family == "gpt2":
        from dsml_tpu.models.gpt2 import GPT2, GPT2Config

        cfg = GPT2Config.by_name(name, **tiny_kwargs)
        return GPT2(cfg), cfg
    raise ValueError(f"unknown family {family!r}; choose gpt2 | llama")
