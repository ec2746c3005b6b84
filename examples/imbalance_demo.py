"""The paper's core phenomenon, live on 8 CPU devices: skewed expert loads
straggle EP; FSSDP's sparse materialization recovers the balance.

Measured from REAL runs of the shard_map FSSDP layer (MoEAux.device_loads —
tokens actually processed per expert-parallel device):

  * EP, uniform router   — even at init a random router is imbalanced
                           (paper Fig. 3);
  * EP, skewed router    — the hot experts' owner becomes the straggler;
  * FSSDP (Alg 1 + Alg 2)— replicas of hot experts flatten the per-device
                           load back to ~mean.

Note the heterogeneous sharding (Algorithm 2) in the FSSDP plan: with the
static-ring materialization, two hot experts co-owned by one device would
compete for the single per-destination slot fed by that owner — Alg 2
separates hot experts across owners, which is what makes the ring schedule
effective (DESIGN.md §2).

  PYTHONPATH=src python examples/imbalance_demo.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.common.config import ModelConfig, MoEConfig
from repro.core import moe as moe_core
from repro.core.moe import MoERuntime, PlanArrays
from repro.core.placement import ep_materialization, homogeneous_sharding
from repro.core.schedule import heterogeneous_sharding, sparse_materialization

EP, T, E = 8, 4096, 16


def main():
    cfg = ModelConfig(
        name="demo", arch_type="moe", num_layers=1, d_model=128,
        num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=1024,
        moe=MoEConfig(num_experts=E, experts_per_token=2, d_ff=256),
        dtype="float32")
    mesh = jax.make_mesh((1, EP), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    buf = jax.random.normal(
        key, (moe_core.buffer_rows(cfg, EP), moe_core.chunk_len(cfg))) * 0.05
    x = jax.random.normal(key, (T, cfg.d_model)) + 2.0
    wr_u = jax.random.normal(key, (cfg.d_model, E)) * 0.01
    wr_s = wr_u.at[:, :2].set(8.0 / (2.0 * cfg.d_model))

    def run(wr, plan, capacity=2048):
        pa = PlanArrays(**jax.tree.map(
            lambda a: a[0], moe_core.plan_to_arrays(plan)._asdict()))
        rt = MoERuntime(mesh=mesh, batch_axes=("data",), impl=plan.impl,
                        m=plan.m, capacity=capacity,
                        local_first=(plan.m == 0))
        xs = jax.device_put(x, NamedSharding(mesh, P(("data", "model"),
                                                     None)))
        bufs = jax.device_put(buf, NamedSharding(mesh, P("model", "data")))
        _, aux = jax.jit(lambda xx, bb: moe_core.moe_layer(
            cfg, rt, xx, wr, bb, pa))(xs, bufs)
        return np.asarray(aux.device_loads), float(aux.dropped_frac)

    sh = homogeneous_sharding(1, E, EP)
    ep_plan = ep_materialization(sh)
    loads = np.full((1, E), 0.01)
    loads[0, :2] = 1.0
    sh_het = heterogeneous_sharding(loads, EP, t=4)        # Algorithm 2
    fssdp = sparse_materialization(sh_het, loads, t=E, m=6,
                                   impl="ring")            # Algorithm 1

    def show(label, dev, mean):
        bar = "  ".join(f"{int(v):5d}" for v in dev)
        print(f"{label:28s} max={dev.max():6.0f} ({dev.max()/mean:4.1f}x "
              f"mean)  per-device: {bar}")

    mean = T * cfg.moe.experts_per_token / EP
    l_u, _ = run(wr_u, ep_plan)
    l_s, _ = run(wr_s, ep_plan)
    l_f, _ = run(wr_s, fssdp)
    print(f"tokens/step={T}, top-{cfg.moe.experts_per_token} of {E} experts "
          f"on {EP} devices -> mean load {mean:.0f}/device\n")
    show("EP, uniform router", l_u, mean)
    show("EP, skewed router", l_s, mean)
    show("FSSDP(Alg1+Alg2), skewed", l_f, mean)
    print(f"\nEP straggler factor under skew : "
          f"{l_s.max()/l_u.max():.2f}x (paper §1: up to 5.18x)")
    print(f"FSSDP recovery over skewed EP  : {l_s.max()/l_f.max():.2f}x")

    # drops at balanced-load buffer sizing (the quality angle)
    bal_cap = int(1.3 * (T / EP) * 2 / (EP * (E // EP)))
    _, d_ep = run(wr_s, ep_plan, bal_cap)
    _, d_f = run(wr_s, fssdp, bal_cap)
    print(f"\nwith buffers sized for balanced loads (capacity {bal_cap}):")
    print(f"  EP drops {d_ep*100:5.1f}% of expert assignments; "
          f"FSSDP drops {d_f*100:5.1f}%")
    assert l_s.max() / l_f.max() > 2.0


if __name__ == "__main__":
    main()
