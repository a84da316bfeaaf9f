"""The one place where the benchmark touches the program under test. What
belongs to one model family sits in a file of its own, ``families/<family>.py``,
found by the configuration file's ``family``: it turns the file's sizes into
the program's own model object and says how that model is trained (``Task``).
A later PR adds a family by adding a file.
"""

from __future__ import annotations

from harness import common


def of(config: dict):
    return common.load_module("harness", "families", config["family"] + ".py")


def build_model(config: dict, **kw):
    return of(config).build_model(config, **kw)


def init_shapes(config: dict, model):
    """The shapes of the model's variables, nothing computed."""
    return of(config).init_shapes(config, model)


def task(config: dict, job: dict):
    return of(config).Task(config, job)


def dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def param_dtype(config: dict):
    return dtype(config["param_dtype"])
