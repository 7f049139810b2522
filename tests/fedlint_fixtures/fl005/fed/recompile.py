"""FL005 fixture: recompile-safety violations."""
import jax.numpy as jnp


def bad_cache_key(arr, table):
    return table[arr.tobytes()]     # VIOLATION: tobytes key outside WaveStager


def bad_shape(items):
    return jnp.stack([jnp.zeros(3) for _ in items])   # VIOLATION: comprehension shape


class WaveStager:
    def stage(self, plan):
        return plan.slot_client.tobytes()     # ok: blessed wave staging path

    def prefetch(self, plan):
        return plan.slot_client.tobytes()     # ok: blessed wave staging path
