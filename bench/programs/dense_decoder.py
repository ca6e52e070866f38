"""How the program under test is built for a dense decoder configuration:
its ``ModelConfig`` from the configuration file's published keys, its
params drawn by the benchmark from the seed, and its serving engine.

Params are the benchmark's, not the program's init: one jitted program draws
every leaf of the program's param layout on the device, in the dtype they are
served in, at fan-in scale.  The program's own init draws the head-split
attention projections at 1/sqrt(head count), which makes random-init softmax a
hard argmax; at fan-in scale attention is soft and a logits comparison with a
reference means something.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1   # stored norm params are (weight - 1) and biases


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"dense_decoder runs a SwiGLU MLP, not {cfg['hidden_act']!r}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg.get("head_dim") or d // h,
        mlp="swiglu", norm="layernorm" if "layer_norm_eps" in cfg else "rmsnorm",
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"],
    )


def build(cfg: dict):
    from repro.models.registry import build_model

    return build_model(model_config(cfg))


def _std(path: str, shape) -> float:
    """1/sqrt(fan-in) for every matmul weight, unit embeddings, and small
    random norm params (stored as weight - 1, and biases)."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf.startswith(("ln", "final_norm")):
        return NORM_STD
    if path == "embed":
        return 1.0
    if leaf in ("wq", "wk", "wv"):                      # (L, d, heads, hd)
        fan_in = shape[-3]
    elif leaf == "wo" and "/mlp/" not in path:          # (L, heads, hd, d)
        fan_in = shape[-3] * shape[-2]
    elif leaf in ("wi", "wg", "wo", "lm_head"):         # (..., fan_in, out)
        fan_in = shape[-2]
    else:
        raise ValueError(f"no draw rule for param {path!r}")
    return fan_in ** -0.5


def param_key(seed: int):
    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(state) & 0x7FFFFFFF)


def init_params(model, seed: int, device=None):
    """Every param leaf drawn from ``seed`` by one jitted program."""
    abstract = model.abstract_params()
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)
    paths = ["/".join(k.key for k in p) for p, _ in flat]

    def draw(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, path, (_, sds) in zip(keys, paths, flat):
            x = jax.random.normal(k, sds.shape, jnp.float32) * _std(path, sds.shape)
            out.append(x.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    shard = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(draw, out_shardings=shard)(param_key(seed))


def engine(model, params, cfg: dict):
    """The serving engine as a deployment runs it: CNA admission, the
    bucketed/packed/AOT-warmed prefill path, a slot cache."""
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import CNAScheduler

    s = cfg["serving"]
    return DecodeEngine(
        model, params, n_slots=s["n_slots"], cache_len=s["cache_len"],
        scheduler=CNAScheduler(), batching=True, pack_width=s["pack_width"],
    )


def counters(engine) -> dict:
    """The engine's own counters, as its metrics registry reads them, and
    its programs' trace counts."""
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    engine.register_metrics(reg)
    out = reg.collect()
    out["engine_compile_counts"] = dict(engine.compile_counts)
    return out
