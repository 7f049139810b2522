"""FedSiKD at LLM scale: cluster-parallel teacher->student distillation with
``launch/steps.make_fedsikd_distill_step``, on 8 placeholder devices with a
reduced config.

4 client replicas (dp axis) in 2 clusters distill a frozen full-depth
teacher into depth-pruned students; intra-cluster gradient aggregation is
the averaging-matrix contraction that lowers to grouped collectives.

  PYTHONPATH=src python examples/llm_distill.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import token_stream
from repro.launch import steps as st
from repro.models import transformer as tf


def main():
    cfg = dataclasses.replace(get_config("qwen2.5-3b", smoke=True),
                              num_layers=2, remat=False)
    D = 4                                    # client replicas on the dp axis
    cluster_of = np.array([0, 0, 1, 1])
    mesh = jax.make_mesh((D, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    dstep, sync, init_students, opt, s_cfg = st.make_fedsikd_distill_step(
        cfg, cluster_of, lr=3e-3, kd_alpha=0.5)
    print(f"teacher: {cfg.num_layers}L/{cfg.d_model}d "
          f"({cfg.param_count()/1e6:.1f}M params) -> student: "
          f"{s_cfg.num_layers}L ({s_cfg.param_count()/1e6:.1f}M params)")

    key = jax.random.PRNGKey(0)
    teacher = tf.init_lm(key, cfg)
    students = init_students(jax.random.fold_in(key, 1))
    opt_state = jax.vmap(opt.init)(students)

    with mesh:
        jstep = jax.jit(dstep)
        B, S = 4, 64
        losses = []
        for rnd in range(3):                           # 3 FL rounds
            for i, b in enumerate(token_stream(cfg.vocab_size, D * B, S,
                                               seed=rnd, num_batches=10)):
                batch = {k: jnp.asarray(v).reshape((D, B) + v.shape[1:])
                         for k, v in b.items()}
                students, opt_state, loss = jstep(students, opt_state,
                                                  teacher, batch)
                losses.append(float(loss))
            students = jax.jit(sync)(students)          # two-level global mean
            print(f"round {rnd}: loss {losses[-10]:.3f} -> {losses[-1]:.3f} "
                  "(post-sync replicas equal: "
                  f"{bool(jnp.allclose(students['embed'][0], students['embed'][-1], atol=1e-5))})")


if __name__ == "__main__":
    main()
