"""Faults planted under the timed path, to show that ``correct`` catches
them: each wraps the program's step function (state, batch) -> state."""
import jax
import jax.numpy as jnp


def unchanged(step, workers):
    """A step that returns its state unchanged."""
    return lambda state, batch: state


def half_batch(step, workers):
    """Half of each worker's batch left out, the mean taken over the rest
    (half of the sequence where a worker holds one row)."""
    def run(state, batch):
        t = batch["tokens"]
        w = t.reshape((workers, -1) + t.shape[1:])
        w = w[:, : w.shape[1] // 2] if w.shape[1] > 1 \
            else w[:, :, : t.shape[1] // 2]
        return step(state, {"tokens": w.reshape((-1,) + w.shape[2:])})
    return run


def altered(step, workers):
    """An answer altered where it is produced: the largest leaf of the
    estimator the step returns, doubled."""
    def run(state, batch):
        new = step(state, batch)
        leaves, treedef = jax.tree_util.tree_flatten(new.g)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves[big] = (leaves[big].astype(jnp.float32) * 2).astype(
            leaves[big].dtype)
        return new._replace(g=jax.tree_util.tree_unflatten(treedef, leaves))
    return run


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}
