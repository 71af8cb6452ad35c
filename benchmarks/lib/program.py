"""From a configuration file (published key names) to the program's own
config object.  The only place the benchmark spells the program's field
names; ``program_fields`` in the file passes further fields through
verbatim, so a configuration that needs one is a data file."""

from __future__ import annotations

from typing import Any, Dict

_PUBLISHED_TO_PROGRAM = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "intermediate_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def llama_fields(config: Dict[str, Any]) -> Dict[str, Any]:
    if config.get("hidden_act", "silu") != "silu" or config.get("bias"):
        raise ValueError(f"{config['name']}: models/llama.py computes "
                         f"SwiGLU without biases only")
    fields = {ours: config[theirs]
              for theirs, ours in _PUBLISHED_TO_PROGRAM.items()}
    fields.update(config.get("program_fields", {}))
    return fields


def llama_config(config: Dict[str, Any], **overrides):
    from ray_tpu.models import llama

    return llama.LlamaConfig(**{**llama_fields(config), **overrides})


def install_preset(config: Dict[str, Any]) -> str:
    """``LLMServer`` takes a preset NAME (``getattr(LlamaConfig, name)``),
    so the configuration is installed as a classmethod under its own name.
    What only a program change can clean: ``LLMServer(config=...)``."""
    from ray_tpu.models import llama

    fields = llama_fields(config)

    def preset(cls, **kw):
        return cls(**{**fields, **kw})

    setattr(llama.LlamaConfig, config["name"], classmethod(preset))
    return config["name"]
