"""DQN on CartPole (↔ rl4j-examples' QLearning cartpole lead example).

Trains QLearningDiscrete (double-DQN + target network + replay) on the
built-in pure-numpy CartPole, then reports greedy-policy episode returns.
Swap ``CartPole()`` for ``GymEnv(name="CartPole-v1")`` (gymnasium
installed) or a ``MalmoStyleEnv``/``FrameStackEnv`` pixel pipeline — the
MDP protocol is the same one rl4j's connectors used.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import numpy as np

from deeplearning4j_tpu.rl import CartPole, QLearningConfig, QLearningDiscrete


def main(quick: bool = False):
    env = CartPole(seed=0, max_steps=200)
    cfg = QLearningConfig(
        gamma=0.99, learning_rate=1e-3, batch_size=64,
        warmup_steps=200, target_update_every=200,
        eps_anneal_steps=1000 if quick else 2000, hidden=(64, 64), seed=0)
    agent = QLearningDiscrete(env, cfg)
    agent.train(max_steps=3500 if quick else 8000)

    returns = []
    for ep in range(5):
        e = CartPole(seed=100 + ep, max_steps=200)
        obs, done, total = e.reset(), False, 0.0
        while not done:
            q = agent.q_values(obs)
            obs, r, done, _ = e.step(int(np.argmax(q)))
            total += r
        returns.append(total)
    print("greedy returns:", returns)
    # an untrained policy balances ~10-30 steps; learning shows clearly
    floor = 40 if quick else 120
    assert np.mean(returns) > floor, returns


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
