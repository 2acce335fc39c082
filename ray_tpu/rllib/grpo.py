"""GRPO: group-relative policy optimization for LLM post-training.

Reference counterpart: the fork's RLHF/GRPO focus (rllib on LLM policies;
group-relative advantage as in DeepSeekMath). Per prompt we sample a
GROUP of completions, score them with a reward function, and use
within-group normalized rewards as per-sequence advantages — no value
net. The policy update is a token-level clipped surrogate with a k3 KL
penalty against a frozen reference policy, all in one jitted step.

TPU-first notes: sampling batches all groups together ([P*G, T] forward
per step — MXU-friendly); the update runs on padded fixed shapes so XLA
compiles one program regardless of completion lengths.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax


@dataclasses.dataclass
class GRPOConfig:
    group_size: int = 8
    clip_param: float = 0.2
    kl_coeff: float = 0.04
    lr: float = 1e-5
    grad_clip: float = 1.0
    num_epochs: int = 1
    temperature: float = 1.0
    max_new_tokens: int = 32
    seed: int = 0


def group_relative_advantages(rewards: np.ndarray,
                              group_size: int) -> np.ndarray:
    """[P*G] rewards -> [P*G] advantages, normalized within each group."""
    r = rewards.reshape(-1, group_size)
    mean = r.mean(axis=1, keepdims=True)
    std = r.std(axis=1, keepdims=True)
    return ((r - mean) / (std + 1e-6)).reshape(-1).astype(np.float32)


def _token_logps(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """logits [B,T,V] predicts tokens[:,1:]; returns [B,T-1] log-probs."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return jnp.take_along_axis(
        logp, tokens[:, 1:, None].astype(jnp.int32), axis=-1).squeeze(-1)


class GRPOLearner:
    """Jitted GRPO update over padded token batches.

    apply_fn(params, tokens[B,T]) -> logits [B,T,V]  (causal LM).
    Batch columns: tokens [B,T] int32, mask [B,T-1] float32 (1 where
    position t+1 is a completion token to train on), old_logps [B,T-1],
    ref_logps [B,T-1], advantages [B].
    """

    def __init__(self, apply_fn: Callable, params, cfg: GRPOConfig):
        self.cfg = cfg
        self.params = params
        self.tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip),
                              optax.adamw(cfg.lr))
        self.opt_state = self.tx.init(params)

        def loss_fn(p, batch):
            logits = apply_fn(p, batch["tokens"]) / cfg.temperature
            logps = _token_logps(logits, batch["tokens"])
            mask = batch["mask"]
            ratio = jnp.exp(logps - batch["old_logps"])
            adv = batch["advantages"][:, None]
            surr = jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1 - cfg.clip_param,
                         1 + cfg.clip_param) * adv)
            # k3 KL estimator vs frozen reference (Schulman)
            logr = batch["ref_logps"] - logps
            kl = jnp.exp(logr) - logr - 1.0
            denom = jnp.maximum(mask.sum(), 1.0)
            pg_loss = -(surr * mask).sum() / denom
            kl_loss = (kl * mask).sum() / denom
            loss = pg_loss + cfg.kl_coeff * kl_loss
            return loss, {"pg_loss": pg_loss, "kl": kl_loss}

        def update(params, opt_state, batch):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, dict(stats, total_loss=loss)

        self._update = jax.jit(update)
        self._apply = jax.jit(lambda p, t: apply_fn(p, t) / cfg.temperature)

    def token_logps(self, params, tokens: np.ndarray) -> np.ndarray:
        return np.asarray(_token_logps(self._apply(params, tokens),
                                       jnp.asarray(tokens)))

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        self.params, self.opt_state, stats = self._update(
            self.params, self.opt_state, batch)
        return {k: float(v) for k, v in stats.items()}


class EngineSampler:
    """Group sampling through the serve LLM engine (SURVEY R7: "LLM
    policy sampled via serve engine").

    The engine gives GRPO the production decode path — slot KV cache,
    continuous batching, pipelined host loop — instead of the naive
    full-forward sampling loop, so one group of G completions costs G
    cache-decode streams, not G*T full forwards. The trainer pushes the
    freshly-updated policy params into the engine after every step."""

    def __init__(self, model, params, cfg: GRPOConfig, *,
                 eos_id: Optional[int] = None, max_seq_len: int = 512,
                 engine_cfg=None):
        from ..serve.llm import LLMEngine, LLMEngineConfig  # noqa: PLC0415
        if engine_cfg is None:
            # KV pool at the defaults: 64-token pages, every slot can
            # reach max_seq_len
            engine_cfg = LLMEngineConfig(
                max_slots=min(16, max(2, cfg.group_size)),
                max_seq_len=max_seq_len,
                prefill_buckets=(16, 32, 64, 128, 256),
                max_new_tokens_default=cfg.max_new_tokens,
                eos_token_id=eos_id)
        self.cfg = cfg
        self.engine = LLMEngine(model, params, engine_cfg)

    def __call__(self, prompt_ids: Sequence[int], group: int) -> np.ndarray:
        cfg = self.cfg
        plen = len(prompt_ids)
        if plen + cfg.max_new_tokens > self.engine.cfg.max_seq_len:
            # The engine would silently clamp the budget and the trainer
            # would then score/train phantom pad tokens — fail loud.
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({cfg.max_new_tokens})"
                f" exceeds engine max_seq_len "
                f"({self.engine.cfg.max_seq_len}); raise max_seq_len")
        eos = self.engine.cfg.eos_token_id
        rids = [self.engine.submit(prompt_ids,
                                   max_new_tokens=cfg.max_new_tokens,
                                   temperature=max(cfg.temperature, 1e-4))
                for _ in range(group)]
        toks = np.zeros((group, plen + cfg.max_new_tokens), np.int32)
        toks[:, :plen] = np.asarray(prompt_ids, np.int32)
        for g, rid in enumerate(rids):
            comp = list(self.engine.stream(rid))
            toks[g, plen:plen + len(comp)] = comp
            if len(comp) < cfg.max_new_tokens and eos is not None:
                # short (EOS-terminated) completion: pad with EOS so the
                # trainer's mask ends at the true completion length
                toks[g, plen + len(comp):] = eos
        return toks

    def set_params(self, params) -> None:
        # Engine dispatches read self.params per call; swapping the pytree
        # between steps is safe (in-flight steps keep the old tree).
        self.engine.params = params

    def shutdown(self) -> None:
        self.engine.shutdown()


class GRPOTrainer:
    """Sample -> score -> group-normalize -> update loop for a causal LM.

    Pass `model=` (a Llama-family module with the KV-cache apply
    contract) and sampling defaults to the serve LLM engine
    (EngineSampler); `apply_fn` is derived from it when omitted. A custom
    `sampler(prompt_ids, group) -> [G, T] tokens` overrides; with neither
    model nor sampler, a plain jitted full-forward loop samples.
    reward_fn(prompt_ids, completion_ids) -> float.
    """

    def __init__(self, apply_fn: Optional[Callable] = None, params=None,
                 reward_fn: Callable = None,
                 cfg: Optional[GRPOConfig] = None, *,
                 eos_id: Optional[int] = None,
                 sampler: Optional[Callable] = None,
                 model=None, max_seq_len: int = 512):
        self.cfg = cfg or GRPOConfig()
        if apply_fn is None:
            if model is None:
                raise ValueError("need apply_fn or model")
            def apply_fn(p, t, _m=model):  # noqa: E306
                out = _m.apply({"params": p}, t)
                return out[0] if isinstance(out, tuple) else out
        self.learner = GRPOLearner(apply_fn, params, self.cfg)
        self.ref_params = jax.device_get(params)   # frozen reference
        self.reward_fn = reward_fn
        self.eos_id = eos_id
        if sampler is None and model is not None:
            sampler = EngineSampler(model, params, self.cfg, eos_id=eos_id,
                                    max_seq_len=max_seq_len)
        self.sampler = sampler
        self._rng = jax.random.PRNGKey(self.cfg.seed)
        self._apply = self.learner._apply

        def sample_step(params, tokens, t, key):
            logits = self._apply(params, tokens)
            return jax.random.categorical(key, logits[:, t - 1], axis=-1)

        self._sample_step = jax.jit(sample_step)

    @property
    def params(self):
        return self.learner.params

    def _sample_group(self, prompt_ids: Sequence[int],
                      group: int) -> np.ndarray:
        """[G, len(prompt)+max_new] greedy-temp sampled completions."""
        cfg = self.cfg
        plen = len(prompt_ids)
        T = plen + cfg.max_new_tokens
        toks = np.zeros((group, T), np.int32)
        toks[:, :plen] = np.asarray(prompt_ids, np.int32)
        for t in range(plen, T):
            self._rng, key = jax.random.split(self._rng)
            nxt = np.asarray(self._sample_step(self.params,
                                               jnp.asarray(toks), t, key))
            toks[:, t] = nxt
        return toks

    def step(self, prompts: List[Sequence[int]]) -> Dict[str, Any]:
        """One GRPO iteration over a list of tokenized prompts."""
        cfg = self.cfg
        G = cfg.group_size
        all_toks, all_masks, rewards = [], [], []
        max_t = 0
        for p in prompts:
            if self.sampler is not None:
                toks = np.asarray(self.sampler(p, G))
            else:
                toks = self._sample_group(p, G)
            plen = len(p)
            mask = np.zeros((G, toks.shape[1] - 1), np.float32)
            for g in range(G):
                comp = toks[g, plen:]
                end = len(comp)
                if self.eos_id is not None:
                    hits = np.nonzero(comp == self.eos_id)[0]
                    if len(hits):
                        end = int(hits[0]) + 1
                # mask[t] trains the prediction of token t+1
                mask[g, plen - 1: plen - 1 + end] = 1.0
                rewards.append(float(self.reward_fn(p, comp[:end])))
            all_toks.append(toks)
            all_masks.append(mask)
            max_t = max(max_t, toks.shape[1])
        toks = np.concatenate([
            np.pad(t, ((0, 0), (0, max_t - t.shape[1]))) for t in all_toks])
        masks = np.concatenate([
            np.pad(m, ((0, 0), (0, max_t - 1 - m.shape[1])))
            for m in all_masks])
        rewards = np.asarray(rewards, np.float32)
        adv = group_relative_advantages(rewards, G)
        old_logps = self.learner.token_logps(self.params, toks)
        ref_logps = self.learner.token_logps(self.ref_params, toks)
        batch = {"tokens": toks, "mask": masks, "old_logps": old_logps,
                 "ref_logps": ref_logps, "advantages": adv}
        stats: Dict[str, float] = {}
        for _ in range(cfg.num_epochs):
            stats = self.learner.update(batch)
        if self.sampler is not None and hasattr(self.sampler, "set_params"):
            self.sampler.set_params(self.params)  # next group: new policy
        return {"reward_mean": float(rewards.mean()),
                "reward_std": float(rewards.std()), **stats}

    def shutdown(self) -> None:
        if self.sampler is not None and hasattr(self.sampler, "shutdown"):
            self.sampler.shutdown()


def make_lora_grpo_trainer(model, base_params, lora, reward_fn, *,
                           cfg: Optional[GRPOConfig] = None,
                           eos_id: Optional[int] = None,
                           max_seq_len: int = 512) -> GRPOTrainer:
    """GRPO post-training over LoRA ADAPTERS: the policy update touches
    only the adapter pytree (optimizer state O(adapter)), the frozen
    base keeps its shardings, and sampling still runs through the serve
    engine — the engine receives the merged weights after every step.
    The KL reference is the initial (zero-delta) policy.

    Standard recipe composition: train/lora.py provides the adapters;
    this wires them into the GRPO loop end-to-end.
    """
    from ..train.lora import merge_lora  # noqa: PLC0415

    meta = {"rank": lora["rank"], "alpha": lora["alpha"]}

    def apply_fn(adapters, tokens):
        merged = merge_lora(base_params, {**meta, "adapters": adapters})
        out = model.apply({"params": merged}, tokens)
        return out[0] if isinstance(out, tuple) else out

    trainer = GRPOTrainer(apply_fn=apply_fn, params=lora["adapters"],
                          reward_fn=reward_fn, cfg=cfg, eos_id=eos_id)
    sampler = EngineSampler(model, merge_lora(base_params, lora),
                            cfg or trainer.cfg, eos_id=eos_id,
                            max_seq_len=max_seq_len)
    push_merged = sampler.set_params

    def set_params(adapters):
        push_merged(merge_lora(base_params,
                               {**meta, "adapters": adapters}))

    sampler.set_params = set_params
    trainer.sampler = sampler
    return trainer
