"""What the builders share: dtypes by name, the training system's handle."""
import itertools

DTYPES = {"bfloat16": "bfloat16", "bf16": "bfloat16", "float32": "float32",
          "fp32": "float32"}


def dtype(name):
    import jax.numpy as jnp

    return getattr(jnp, DTYPES[name])


def program_seed(seed):
    """The seed the program's own initialiser gets for the weights: the
    run's seed folded into 31 bits."""
    return int(seed) % (2 ** 31 - 1)


class TrainSystem:
    """A training engine behind the three calls a traffic kind makes."""

    def __init__(self, engine, info):
        self.engine = engine
        self.info = info
        self._batches = None

    def load(self, batch):
        self._batches = itertools.repeat(batch)

    def step(self):
        return self.engine.train_batch(self._batches)

    def fence(self, value):
        import jax

        jax.block_until_ready(value)


def engine_config(env, plan):
    """The configuration's ``ds_config`` with the traffic's micro batch."""
    ds = dict(env.config["train"]["ds_config"])
    ds["train_micro_batch_size_per_gpu"] = \
        int(env.traffic["micro_batch_per_chip"])
    ds["gradient_accumulation_steps"] = 1
    return ds
