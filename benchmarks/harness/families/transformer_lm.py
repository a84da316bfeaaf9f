"""``chainermn_tpu.models.TransformerLM`` from a configuration's sizes, and
its trainer: ``jit_lm_train_step`` on packed sequences of random token ids."""

from __future__ import annotations

from harness import families, work


def build_model(config: dict, **kw):
    from chainermn_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["d_model"],
        n_heads=config["n_heads"], n_layers=config["n_layers"],
        d_ff=config["d_ff"], max_len=config["max_len"],
        compute_dtype=families.dtype(config["compute_dtype"]), **kw)


def init_shapes(config: dict, model):
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


class Task:
    def __init__(self, config: dict, job: dict) -> None:
        self.config, self.job = config, job

    def optimizer_target(self, variables):
        return variables                    # the LM step updates the whole tree

    def batch(self, key, n_rows: int):
        import jax
        import jax.numpy as jnp

        toks = jax.random.randint(
            key, (n_rows, self.job["seq_len"] + 1), 0,
            self.config["vocab_size"], jnp.int32)
        return toks[:, :-1], toks[:, 1:]

    def build_step(self, model, opt, comm):
        from chainermn_tpu.training import jit_lm_train_step

        return jit_lm_train_step(model, opt, comm)

    def flops_per_step(self, n_rows: int) -> float:
        return work.lm_train_flops_per_step(
            self.config, n_rows, self.job["seq_len"])

    def attention_work_per_step(self, n_rows: int):
        return work.causal_attention_train(
            self.config, n_rows, self.job["seq_len"])
