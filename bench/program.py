"""The system under test, as the benchmark reaches it: the model
configuration built from a configuration file, and the model whose
weights are the benchmark's own. Everything else comes from the
program's launchers unchanged."""
from __future__ import annotations

import dataclasses
import os
import sys

from bench import harness
from bench import weights
from bench.reference import model as ref_model

# Hugging Face key -> field of repro's ModelConfig
_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "head_dim": "head_dim",
}


def import_program(root: str = harness.ROOT):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise harness.SetupError(f"bench: the program (src/repro) is not in "
                                 f"{root}")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(conf: dict):
    """repro ModelConfig of a configuration file: the registry entry named
    in ``program.arch`` with every size of ``run``."""
    from repro.configs import get_config
    prog = conf["program"]
    cfg = get_config(prog["arch"])
    sizes = {f: conf["run"][k] for k, f in _FIELDS.items() if k in conf["run"]}
    if conf["run"].get("sliding_window") is not None:
        raise ValueError("sliding windows are not wired to the program")
    return dataclasses.replace(cfg, window=None, pattern=None,
                               **{k: v for k, v in prog.items()
                                  if k != "arch"}, **sizes)


def shapes(conf: dict) -> dict:
    return ref_model.param_shapes(ref_model.dims(conf["run"]))


def seeded_model(cfg, shapes_tree, key, dtype):
    """A repro Model whose ``init`` returns the benchmark's weights from
    ``key`` (checked leaf for leaf against the program's own layout)."""
    import jax
    from repro.models.model import Model

    own = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: weights.make_params(shapes_tree, key,
                                                      dtype))
    if (jax.tree.structure(own) != jax.tree.structure(ours)
            or [x.shape for x in jax.tree.leaves(own)]
            != [x.shape for x in jax.tree.leaves(ours)]):
        raise harness.SetupError(
            "bench: the program's parameter layout differs from the "
            f"benchmark's: {jax.tree.map(lambda x: x.shape, own)}")

    class SeededModel(Model):
        def init(self, _key=None):
            return weights.make_params(shapes_tree, key, dtype)

    return SeededModel(cfg)
