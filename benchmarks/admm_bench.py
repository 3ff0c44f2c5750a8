"""ADMM pruning benchmark: convergence + quality-vs-sparsity tradeoff
(the paper's section 2 as a table; their accuracy tables are qualitative
"satisfied output", our proxy is recoverable-regression loss).

Setup: block-sparse teacher, dense student; report the final primal residual
and the post-hard-prune loss ratio vs the dense-trained floor at each
sparsity -- ADMM should be near-loss-neutral up to the teacher's sparsity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pruning import (
    AdmmConfig,
    Block,
    PrunePlan,
    admm_init,
    admm_penalty,
    admm_update,
    convergence_metrics,
    hard_prune,
)
from repro.utils.compile_cache import enable_compile_cache


def run_admm(sparsity: float, steps: int = 300, d: int = 64):
    key = jax.random.PRNGKey(0)
    wtrue, _ = (lambda w: (w, None))(jax.random.normal(jax.random.PRNGKey(2), (d, d)))
    from repro.core.pruning import project

    wtrue, _ = project(wtrue, Block(0.5, bm=8, bn=8))
    x = jax.random.normal(jax.random.PRNGKey(1), (512, d))
    y = x @ wtrue

    def loss_fn(p):
        return jnp.mean((x @ p["w"] - y) ** 2)

    plan = PrunePlan.from_rules([("*", Block(sparsity, bm=8, bn=8))], min_size=16)
    cfg = AdmmConfig(rho=0.3, rho_ramp=1.1, rho_max=3.0, update_every=1)
    params = {"w": jax.random.normal(key, (d, d)) * 0.1}
    state = admm_init(params, plan, cfg)

    def total(p, s):
        return loss_fn(p) + admm_penalty(p, s)

    step = jax.jit(
        lambda p, s: jax.tree.map(lambda a, g: a - 2e-2 * g, p, jax.grad(total)(p, s))
    )
    p = params
    for it in range(steps):
        p = step(p, state)
        if it % 10 == 9:
            state = admm_update(p, state, cfg)
    res = float(convergence_metrics(p, state)["primal_residual"])
    pruned, _ = hard_prune(p, state)
    # dense floor: same budget without ADMM
    pd = params
    stepd = jax.jit(lambda p: jax.tree.map(lambda a, g: a - 2e-2 * g, p, jax.grad(loss_fn)(p)))
    for _ in range(steps):
        pd = stepd(pd)
    return res, float(loss_fn(pruned)), float(loss_fn(pd))


def main():
    enable_compile_cache()
    print("admm,sparsity,primal_residual,pruned_loss,dense_loss,ratio")
    for sp in (0.25, 0.5, 0.75):
        res, lp, ld = run_admm(sp)
        print(f"admm,{sp},{res:.4f},{lp:.5f},{ld:.5f},{lp / max(ld, 1e-9):.2f}")


if __name__ == "__main__":
    main()
