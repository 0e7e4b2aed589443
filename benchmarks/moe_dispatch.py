"""Beyond-paper: MoE token dispatch — sort-based (paper machinery) vs the
dense one-hot einsum baseline, on the granite smoke config over a (2,4)
(data, model) mesh.  derived = speedup + HLO collective bytes of the
distributed path.  The ``ep_sim_subgroup`` cell runs the same dispatch
body over an *emulated* (d=4, ep=4) mesh via ``comm.sim_map(mesh=...)`` —
16 PEs on 8 devices, each data row sorting within its own expert-parallel
subgroup (the multi-tenant layout).
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config, smoke_variant
from repro.launch import hlo_cost
from repro.models import moe as M

from common import emit, timeit


def main():
    cfg = smoke_variant(get_config("granite-moe-1b-a400m"))
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg.d_model, cfg.d_ff, cfg.n_experts, jnp.float32)
    x = jax.random.normal(key, (4, 64, cfg.d_model), jnp.float32)
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))

    f_dense = jax.jit(lambda xx: M.moe_dense(xx, p, cfg)[0])
    f_local = jax.jit(lambda xx: M.moe_local(xx, p, cfg)[0])
    with mesh:
        f_ep = jax.jit(lambda xx: M.moe_ep_shardmap(
            xx, p, cfg, mesh, data_axes=("data",))[0])
        us_ep = timeit(lambda: np.asarray(f_ep(x)))
        comp = f_ep.lower(x).compile()
    us_dense = timeit(lambda: np.asarray(f_dense(x)))
    us_local = timeit(lambda: np.asarray(f_local(x)))
    a = hlo_cost.analyze(comp.as_text())
    # emulated (d, ep) subgroup mesh: 4 tenants × 4-way expert parallelism
    f_sim = jax.jit(lambda xx: M.moe_ep_sim(xx, p, cfg, d=4,
                                            ep=min(4, cfg.n_experts))[0])
    us_sim = timeit(lambda: np.asarray(f_sim(x)))
    emit("moe/dense_onehot", us_dense, "E×FLOPs baseline")
    emit("moe/local_sortgroup", us_local,
         f"speedup_vs_dense={us_dense / us_local:.2f}x")
    emit("moe/ep_sort_dispatch", us_ep,
         f"a2a_bytes={sum(a['collective_bytes'].values()):.0f}")
    emit("moe/ep_sim_subgroup", us_sim,
         f"mesh=4x{min(4, cfg.n_experts)}_emulated")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
