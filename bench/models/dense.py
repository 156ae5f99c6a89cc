"""Dense decoder family (Qwen2/Qwen3 style): the mapping from a published
``config.json`` plus the split's assumed sizes to the program's model
config and to the numbers the reference and the FLOP count read, and the
family's plain reference (``bench/reference.py``)."""
from __future__ import annotations


def numbers(config: dict) -> dict:
    """The model's sizes under short names, from the configuration file."""
    c, a = config, config["assumed"]
    heads = c["num_attention_heads"]
    return {
        "vocab": c["vocab_size"],
        "d": c["hidden_size"],
        "layers": c["num_hidden_layers"],
        "heads": heads,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "ff": c["intermediate_size"],
        "rope_theta": float(c["rope_theta"]),
        "eps": float(c["rms_norm_eps"]),
        "qk_norm": bool(a["qk_norm"]),
        "qkv_bias": bool(a["qkv_bias"]),
        "cut": a["cut_layer"],
        "aux_rank": a["aux_rank"],
    }


def program_config(config: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import ModelConfig
    m = numbers(config)
    a = config["assumed"]
    return ModelConfig(
        name=config["name"], family="dense", num_layers=m["layers"],
        d_model=m["d"], num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], d_ff=m["ff"], vocab_size=m["vocab"],
        qk_norm=m["qk_norm"], qkv_bias=m["qkv_bias"],
        rope_theta=m["rope_theta"], cut_layer=m["cut"],
        aux_rank=m["aux_rank"], dtype=config["torch_dtype"],
        remat=bool(a["remat"]), swa_window=0)


def reference(numbers: dict, mix: dict, precision: str = "fp32"):
    """The plain reference that replays a cell's rounds for this family:
    ``run(params, traffic, rounds) -> (params, losses)``, after which its
    ``uploads`` lists ``(round, client, upload)`` before the codec."""
    import reference as dense_reference
    return dense_reference.Reference(numbers, mix, precision=precision)
