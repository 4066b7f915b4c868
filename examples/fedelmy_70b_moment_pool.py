"""Beyond-paper example: the moment-form pool at large-model scale.

The paper keeps S+1 full model copies per client — at qwen2-72b scale that
is ~1 TB of pool state. The moment-form statistics (DESIGN.md §3) support
the squared-L2 diversity objective exactly with ONE extra copy. This
example demonstrates both representations agree numerically on a mid-size
model, then prints the memory budgets for the assigned 72B config.

    PYTHONPATH=src python examples/fedelmy_70b_moment_pool.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.api import get_pool_backend
from repro.configs import FedConfig, get_arch
from repro.core import pairwise_distance
from repro.models import build_model


def main():
    # numerical agreement on a real (reduced) transformer
    cfg = get_arch("qwen2-7b").reduced()
    model = build_model(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    members = [model.init(k) for k in keys[:3]]
    live = model.init(keys[3])

    # both representations come from the repro.api pool-backend registry
    fed = FedConfig(pool_size=3, distance_measure="squared_l2")
    mpool = get_pool_backend("moment").create(members[0], fed)
    fpool = get_pool_backend("stacked").create(members[0], fed)
    for m in members[1:]:
        mpool, fpool = mpool.append(m), fpool.append(m)

    moment_msq = float(mpool.mean_sq_distance(live))
    brute_msq = float(np.mean([float(pairwise_distance(live, m, "squared_l2"))
                               for m in members]))
    print(f"mean squared distance: moment-form {moment_msq:.4f} "
          f"vs brute force {brute_msq:.4f} "
          f"(rel err {abs(moment_msq-brute_msq)/brute_msq:.2e})")

    # memory budget at the assigned 72B config
    big = get_arch("qwen2-72b")
    shapes = jax.eval_shape(build_model(big).init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    bytes_per = 2  # bf16
    s = 5
    paper_pool = (s + 1) * n_params * bytes_per
    moment_pool = n_params * (4 + 2)  # f32 mean + bf16 anchor
    print(f"\nqwen2-72b ({n_params/1e9:.1f}B params), pool S={s}:")
    print(f"  paper-faithful pool : {paper_pool/1e12:.2f} TB")
    print(f"  moment-form pool    : {moment_pool/1e9:.1f} GB "
          f"({paper_pool/moment_pool:.1f}x smaller)")
    print(f"  per chip on the 256-chip mesh: "
          f"{paper_pool/256/1e9:.1f} GB vs {moment_pool/256/1e9:.2f} GB "
          f"(v5e HBM = 16 GB)")


if __name__ == "__main__":
    main()
