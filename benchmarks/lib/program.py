"""The program's configuration, from a configuration file.

Every key of the file's ``model``, ``train`` and ``serve`` blocks goes to the
program under its own name, through the program's public constructors:
``LLMConfig`` for a served cell, the ``model`` dictionary of the trainer's
loop configuration for a trained one. The file states its ``family``; the
program decides which it knows, and a key that its constructor does not take
is its error, by the key's name. The harness picks no keys and names no
family, so a configuration of another family is a new file.
"""
from __future__ import annotations

# ``serve`` keys the serving runner reads for itself and the program's
# constructor does not take: the token an answer may end on early
RUNNER_SERVE_KEYS = ("eos_token_id",)


def llm_config(config: dict, **deployment):
    """``LLMConfig`` of a configuration: family, sizes and serving block as
    the file has them. ``deployment`` is what the machine, not the
    configuration, decides (``deployment_config``)."""
    from ray_tpu.llm import LLMConfig

    serve = {k: v for k, v in config.get("serve", {}).items()
             if k not in RUNNER_SERVE_KEYS}
    return LLMConfig(model_id=config["name"], model_family=config["family"],
                     **config["model"], **serve, **deployment)


def trainer_model(config: dict) -> dict:
    """The ``model`` dictionary ``default_jax_train_loop`` builds its model
    configuration from."""
    return {"family": config["family"], **config["model"], **config["train"]}


def model_config(config: dict):
    """The family's own configuration object with the file's sizes, as the
    engine builds it: what ``init_params`` needs. (The trainer parses its
    ``model`` dictionary inline, with no function to call: PERF.md section
    7. Both build the same weights from the same sizes.)"""
    return llm_config(config).model_config()
