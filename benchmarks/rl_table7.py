"""Table 7 — offline RL (D4RL stand-in): Decision-Flowformer.

Train on noisy LQR rollouts; evaluate by ROLLING OUT the learned policy in
the true synthetic environment conditioned on an expert return-to-go —
a real closed-loop control evaluation, not action MSE."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_table, save_table, with_kind
from repro.configs import get_config
from repro.data.synthetic import trajectories
from repro.models import decision
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.training.schedule import warmup_cosine

STATE_DIM, ACTION_DIM, HORIZON = 17, 6, 20


def _env(seed=0):
    rng = np.random.default_rng(seed)
    a_mat = np.eye(STATE_DIM) * 0.95
    b_mat = rng.normal(0, 0.3, (STATE_DIM, ACTION_DIM)) / np.sqrt(ACTION_DIM)
    return a_mat, b_mat


def rollout(params, cfg, *, n_episodes=16, target_rtg=-2.0, seed=0):
    """Closed-loop evaluation in the synthetic env (same dynamics seed as
    the dataset generator in repro/data/synthetic.py)."""
    a_mat, b_mat = _env(0)
    rng = np.random.default_rng(seed)
    s = rng.normal(0, 1, (n_episodes, STATE_DIM)).astype(np.float32)
    states = np.zeros((n_episodes, HORIZON, STATE_DIM), np.float32)
    actions = np.zeros((n_episodes, HORIZON, ACTION_DIM), np.float32)
    rtg = np.full((n_episodes, HORIZON, 1), target_rtg, np.float32)
    total = np.zeros(n_episodes)
    fwd = jax.jit(lambda p, r, st, ac, t: decision.forward(p, r, st, ac, t, cfg))
    for t in range(HORIZON):
        states[:, t] = s
        ts = np.tile(np.arange(HORIZON, dtype=np.int32), (n_episodes, 1))
        pred = np.asarray(fwd(params, jnp.asarray(rtg), jnp.asarray(states),
                              jnp.asarray(actions), jnp.asarray(ts)))
        a = pred[:, t]
        actions[:, t] = a
        r = -(s**2).sum(-1) * 0.05 - 0.1 * (a**2).sum(-1)
        total += r
        rtg[:, t + 1:] = rtg[:, t:t+1] - r[:, None, None]
        s = (s @ a_mat.T + a @ b_mat.T).astype(np.float32)
    return float(total.mean())


def run(*, quick: bool = True) -> dict:
    n_traj, steps = (300, 120) if quick else (5000, 3000)
    data = trajectories(0, n_traj, horizon=HORIZON, state_dim=STATE_DIM,
                        action_dim=ACTION_DIM)
    # behavior-policy average return (the "dataset" row)
    behavior_return = float(data["rewards"].sum(1).mean())
    expert_rtg = float(np.percentile(data["rtg"][:, 0, 0], 95))

    base = get_config("flowformer_dt")
    base = dataclasses.replace(base, n_layers=2, d_model=96, n_heads=4,
                               n_kv_heads=4, d_ff=192)
    # actions_in: shifted so position t sees a_{t-1}
    actions_in = np.concatenate(
        [np.zeros_like(data["actions"][:, :1]), data["actions"][:, :-1]], 1
    )
    rows = {"behavior policy (dataset)": {"avg_return": behavior_return}}
    for kind in ("flow", "softmax", "linear"):
        cfg = with_kind(base, kind, chunk_size=0)
        params = decision.init(jax.random.PRNGKey(0), cfg,
                               state_dim=STATE_DIM, action_dim=ACTION_DIM,
                               max_ep_len=HORIZON)
        opt = adamw_init(params)
        acfg = AdamWConfig(weight_decay=1e-4, grad_clip=0.25)

        @jax.jit
        def step_fn(params, opt, batch, lr):
            (loss, m), g = jax.value_and_grad(
                lambda p: decision.loss_fn(p, batch, cfg), has_aux=True
            )(params)
            p2, o2, _ = adamw_update(g, opt, params, lr, acfg)
            return p2, o2, loss

        rng = np.random.default_rng(0)
        for s in range(steps):
            idx = rng.integers(0, n_traj, 32)
            batch = {
                "rtg": jnp.asarray(data["rtg"][idx]),
                "states": jnp.asarray(data["states"][idx]),
                "actions_in": jnp.asarray(actions_in[idx]),
                "actions": jnp.asarray(data["actions"][idx]),
                "timesteps": jnp.asarray(data["timesteps"][idx]),
            }
            lr = warmup_cosine(jnp.asarray(s), peak_lr=1e-3, warmup=20,
                               total=steps)
            params, opt, loss = step_fn(params, opt, batch, lr)
        ret = rollout(params, cfg, target_rtg=expert_rtg)
        rows[f"decision-{kind}"] = {"avg_return": ret}
    print_table("Table 7 (offline RL stand-in): closed-loop return "
                "(higher=better)", rows, ["avg_return"])
    save_table("rl_table7", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
