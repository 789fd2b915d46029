"""RL: a few PPO iterations on CartPole."""
import _bootstrap  # noqa: F401  (repo-checkout import shim)
# sim-env RL is latency-bound: tiny MLP forwards gain nothing from an
# accelerator (in a cluster, env-runner actors have no TPU chips bound
# anyway). Force CPU: this driver then never takes the node's chip.
import jax
jax.config.update("jax_platforms", "cpu")
import ray_tpu
from ray_tpu.rllib import PPOConfig

if __name__ == "__main__":
    ray_tpu.init(num_cpus=4)
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_envs_per_env_runner=4)
        .training(train_batch_size=1024, minibatch_size=128,
                  num_epochs=4)
        .debugging(seed=0)
        .build_algo()
    )
    for i in range(3):
        r = algo.train()
        print(f"iter {i}: return={r['episode_return_mean']:.1f}")
    algo.stop()
    ray_tpu.shutdown()
