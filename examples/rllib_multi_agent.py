"""RL: multi-agent PPO — two cooperating agents sharing one policy.

Each agent sees a 4-state one-hot observation and earns +1 per step for
matching its action to state % 2. `policy_mapping_fn` routes both agents
onto one shared module (change it to route each agent to its own module
for independent policies).
"""
import _bootstrap  # noqa: F401  (repo-checkout import shim)
# sim-env RL is latency-bound: tiny MLP forwards gain nothing from an
# accelerator (in a cluster, env-runner actors have no TPU chips bound
# anyway). Force CPU: this driver then never takes the node's chip.
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from ray_tpu.rllib import MultiAgentEnv, MultiAgentPPOConfig


class MatchingEnv(MultiAgentEnv):
    possible_agents = ["a0", "a1"]

    def __init__(self):
        import gymnasium as gym

        obs_sp = gym.spaces.Box(0.0, 1.0, (4,), np.float32)
        act_sp = gym.spaces.Discrete(2)
        self.observation_spaces = {a: obs_sp for a in self.possible_agents}
        self.action_spaces = {a: act_sp for a in self.possible_agents}
        self._rng = np.random.default_rng(0)
        self._t = 0
        self._state = {}

    def _obs(self):
        out = {}
        for a in self.possible_agents:
            s = int(self._rng.integers(0, 4))
            self._state[a] = s
            onehot = np.zeros(4, np.float32)
            onehot[s] = 1.0
            out[a] = onehot
        return out

    def reset(self, *, seed=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._t = 0
        return self._obs(), {}

    def step(self, actions):
        rewards = {a: float(int(actions[a]) == self._state[a] % 2)
                   for a in self.possible_agents}
        self._t += 1
        done = self._t >= 8
        terms = {a: done for a in self.possible_agents}
        terms["__all__"] = done
        truncs = {a: False for a in self.possible_agents}
        truncs["__all__"] = False
        return self._obs(), rewards, terms, truncs, {}


if __name__ == "__main__":
    algo = (
        MultiAgentPPOConfig()
        .environment(env=lambda: MatchingEnv())
        .multi_agent(policies={"shared": None},
                     policy_mapping_fn=lambda agent_id: "shared")
        .training(train_batch_size=512, minibatch_size=128,
                  num_epochs=4, lr=3e-3, entropy_coeff=0.01)
        .build_algo()
    )
    for i in range(8):
        r = algo.train()
        print(f"iter {i}: return={r['episode_return_mean']:.1f} "
              f"(optimal 16.0)")
    algo.stop()
