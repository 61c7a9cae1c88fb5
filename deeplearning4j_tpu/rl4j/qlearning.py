"""Sync deep Q-learning (reference ``rl4j-core .../learning/sync/qlearning/
discrete/QLearningDiscreteDense.java``†: DQN over a dense network with
target network, experience replay, double Q-learning, epsilon-greedy).

TPU-first shape: the whole TD update — online forward on obs AND next_obs,
target forward, double-DQN action selection, TD targets, MSE on the taken
actions, gradients and the fused updater sweep — is ONE jitted XLA program
(``_build_update``); the host loop only steps the MDP and fills the
replay buffer. The reference interleaves per-op nd4j calls for the same
math (§3.1 hot-loop contrast).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import updaters as _upd
from .mdp import MDP
from .policy import DQNPolicy, EpsGreedy
from .replay import ExpReplay, Transition


@dataclass
class QLearningConfiguration:
    """Reference ``QLearning.QLConfiguration``† fields that matter here."""
    seed: int = 123
    max_step: int = 5000
    batch_size: int = 32
    target_dqn_update_freq: int = 100
    update_start: int = 64          # replay warmup before learning
    gamma: float = 0.99
    eps_init: float = 1.0
    eps_min: float = 0.05
    eps_decay_steps: int = 1000
    exp_replay_size: int = 10000
    double_dqn: bool = True


class QLearningDiscreteDense:
    """DQN trainer over a MultiLayerNetwork Q-function."""

    def __init__(self, mdp: MDP, network,
                 conf: Optional[QLearningConfiguration] = None):
        self.mdp = mdp
        self.net = network
        self.conf = conf or QLearningConfiguration()
        self.replay = ExpReplay(self.conf.exp_replay_size,
                                self.conf.batch_size, self.conf.seed)
        self.policy = DQNPolicy(network)
        self.explorer = EpsGreedy(self.policy, mdp.n_actions,
                                  self.conf.eps_init, self.conf.eps_min,
                                  self.conf.eps_decay_steps, self.conf.seed)
        self._target_params = jax.tree.map(jnp.copy, network.params)
        self._update = None
        self.step_count = 0       # environment steps
        self.update_count = 0     # gradient updates (drives Adam/schedules)
        self.episode_returns = []
        # set by play(): the shared mdp/history were driven off-policy, so
        # the next train_step must start a fresh episode instead of pairing
        # observations from two unrelated trajectories in the replay buffer
        self._pending_reset = False

    # ------------------------------------------------------------ training
    def _build_update(self):
        net = self.net
        updater = net.conf.updater
        gamma = self.conf.gamma
        double = self.conf.double_dqn

        def q_of(params, x):
            out, _, _ = net._forward(params, x, net.state, train=False,
                                     rng=None)
            return out  # [B, n_actions]

        def update(params, opt_state, target_params, obs, actions, rewards,
                   next_obs, dones, step):
            def loss_fn(p):
                q = q_of(p, obs)
                q_taken = jnp.take_along_axis(
                    q, actions[:, None].astype(jnp.int32), axis=1)[:, 0]
                q_next_t = q_of(target_params, next_obs)
                if double:
                    # double DQN: online net picks, target net evaluates
                    a_star = jnp.argmax(q_of(p, next_obs), axis=1)
                    q_next = jnp.take_along_axis(
                        q_next_t, a_star[:, None], axis=1)[:, 0]
                else:
                    q_next = jnp.max(q_next_t, axis=1)
                td_target = rewards + gamma * (1.0 - dones) * \
                    jax.lax.stop_gradient(q_next)
                return jnp.mean((q_taken - td_target) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = _upd.apply_leafwise(
                updater, grads, opt_state, params, step)
            return new_params, new_opt, loss

        return jax.jit(update, donate_argnums=(0, 1))

    # observation hooks: the conv/pixel subclass stacks frame history here
    def _observe_reset(self, frame):
        return frame

    def _observe_step(self, frame):
        return frame

    def train_step(self) -> Optional[float]:
        """One environment step (+ one learn step once warm). Returns the
        TD loss when a learn step ran."""
        mdp = self.mdp
        if mdp.is_done() or self.step_count == 0 or self._pending_reset:
            self._obs = self._observe_reset(mdp.reset())
            self._ep_ret = 0.0
            self._pending_reset = False
        obs = self._obs
        action = self.explorer.next_action(obs)
        next_frame, reward, done = mdp.step(action)
        next_obs = self._observe_step(next_frame)
        self.replay.store(Transition(obs, action, reward, next_obs, done))
        self._obs = next_obs
        self._ep_ret += reward
        if done:
            self.episode_returns.append(self._ep_ret)
        self.step_count += 1

        loss = None
        if len(self.replay) >= max(self.conf.update_start,
                                   self.conf.batch_size):
            if self._update is None:
                self._update = self._build_update()
            o, a, r, no, d = self.replay.sample()
            # updater step = UPDATE count (not env steps): Adam bias
            # correction and lr schedules key off optimizer steps, same as
            # MultiLayerNetwork.fit's self.iteration
            self.net.params, self.net.updater_state, loss = self._update(
                self.net.params, self.net.updater_state,
                self._target_params, jnp.asarray(o), jnp.asarray(a),
                jnp.asarray(r), jnp.asarray(no), jnp.asarray(d),
                jnp.asarray(self.update_count, jnp.int32))
            self.update_count += 1
            self.net.iteration = self.update_count  # later fit() continues
            if self.step_count % self.conf.target_dqn_update_freq == 0:
                self._target_params = jax.tree.map(jnp.copy, self.net.params)
        return None if loss is None else float(loss)

    def train(self, max_steps: Optional[int] = None) -> "QLearningDiscreteDense":
        """Run the training loop (reference ``Learning.train()``)."""
        for _ in range(max_steps or self.conf.max_step):
            self.train_step()
        return self

    def get_policy(self) -> DQNPolicy:
        return self.policy


class HistoryProcessor:
    """Rolling frame stack (reference ``rl4j-core .../learning/
    HistoryProcessor.java``†: the Atari-style last-N-frames observation).
    ``reset(frame)`` fills the stack with the first frame; ``add(frame)``
    rolls it. Stacked output is [history, H, W] float32 — the channel axis
    a NCHW conv Q-net consumes."""

    def __init__(self, history_length: int = 4):
        self.n = int(history_length)
        self._frames = None

    def reset(self, frame) -> np.ndarray:
        f = np.asarray(frame, np.float32)
        self._frames = [f] * self.n
        return self.get()

    def add(self, frame) -> np.ndarray:
        self._frames = self._frames[1:] + [np.asarray(frame, np.float32)]
        return self.get()

    def get(self) -> np.ndarray:
        return np.stack(self._frames, axis=0)


class QLearningDiscreteConv(QLearningDiscreteDense):
    """DQN over a convolutional Q-net on stacked pixel frames (reference
    ``rl4j-core .../qlearning/discrete/QLearningDiscreteConv.java``†: the
    flagship pixel-DQN entry point — HistoryProcessor frame stack feeding
    a conv net through the same sync double-DQN machinery).

    The MDP must emit 2-D frames [H, W]; observations seen by the replay
    buffer, policy, and the jitted TD update are the stacked
    [history, H, W] arrays. Everything else — replay, target network,
    double-DQN TD update as one XLA program — is inherited unchanged."""

    def __init__(self, mdp: MDP, network,
                 conf: Optional[QLearningConfiguration] = None,
                 history_length: int = 4):
        super().__init__(mdp, network, conf)
        self.history = HistoryProcessor(history_length)

    def _observe_reset(self, frame):
        return self.history.reset(frame)

    def _observe_step(self, frame):
        return self.history.add(frame)

    def play(self, max_steps: int = 1000) -> float:
        """Greedy rollout with the frame stack applied (DQNPolicy.play
        sees raw frames; the conv Q-net needs stacked observations).
        Drives the shared mdp/history, so the trainer is flagged to start
        a fresh episode on the next train_step."""
        obs = self.history.reset(self.mdp.reset())
        total = 0.0
        for _ in range(max_steps):
            a = self.policy.next_action(obs)
            frame, r, done = self.mdp.step(a)
            obs = self.history.add(frame)
            total += r
            if done:
                break
        self._pending_reset = True
        return total
