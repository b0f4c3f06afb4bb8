"""GPT language modeling — the north-star recipe (SURVEY §6).

The reference has no transformer at all (SURVEY §5.7); this recipe is
the framework's stretch case: the full 4-D parallel train step driven
entirely from YAML. ``env.mesh`` picks the topology —

- ``dp``                 : pure data parallel (the reference's world)
- ``dp:2,fsdp:2,tp:2``   : + ZeRO-style weight sharding + Megatron tp
- ``dp:1,fsdp:2,tp:2,sp:2``: + ring-attention sequence parallelism

— and the SAME script runs on one chip, the virtual CPU mesh, or a pod.
Weights/optimizer state are laid out by ``GPT.SHARDING_RULES`` via
``parallel.sharding.shard_state``; the batch is sharded (batch over
dp+fsdp, sequence over sp); XLA compiles the matching collectives into
the step.

Run from this directory: ``python gpt.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from tqdm import tqdm

import torchbooster_tpu.distributed as dist
import torchbooster_tpu.utils as utils
from torchbooster_tpu.config import (
    BaseConfig,
    DatasetConfig,
    EnvConfig,
    LoaderConfig,
    OptimizerConfig,
    SchedulerConfig,
)
from torchbooster_tpu.dataset import Split
from torchbooster_tpu.metrics import MetricsAccumulator
from torchbooster_tpu.models import GPT
from torchbooster_tpu.models.gpt import GPTConfig
from torchbooster_tpu.ops.losses import (cross_entropy,
                                         lm_head_cross_entropy)


@dataclass
class ModelConfig(BaseConfig):
    """GPT dims, YAML-driven (a user config subclass resolved by name)."""

    vocab: int = 1_024
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 8
    n_kv_heads: int = 0             # grouped-query attention (0 = MHA)
    seq_len: int = 256
    remat: bool = True
    n_experts: int = 0              # > 0: MoE blocks over the ep axis
    top_k: int = 2                  # experts per token
    capacity_factor: float = 1.25   # static per-expert buffer slack
    aux_weight: float = 1e-2        # load-balance loss weight
    # sequence-parallel attention on sp>1 meshes: auto | ring | ulysses
    sp_strategy: str = "auto"
    pos: str = "learned"            # position encoding: learned | rope
    mlp: str = "gelu"               # MLP flavor: gelu | swiglu
    dropout: float = 0.0            # residual/embedding dropout (train)
    # stream tokens through the LM head (ops.losses.lm_head_cross_
    # entropy) instead of materializing the (T, vocab) logits — the
    # recorded +6.7% winner at S=1024, bigger at long S
    chunked_head: bool = False

    def make(self) -> GPTConfig:
        return GPTConfig(vocab=self.vocab, n_layers=self.n_layers,
                         d_model=self.d_model, n_heads=self.n_heads,
                         n_kv_heads=self.n_kv_heads,
                         seq_len=self.seq_len, n_experts=self.n_experts,
                         top_k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         sp_strategy=self.sp_strategy, pos=self.pos,
                         mlp=self.mlp, dropout=self.dropout)


@dataclass
class Config(BaseConfig):
    n_iter: int
    seed: int
    clip: float
    accumulate_every: int
    log_every: int
    save_every: int                 # 0 disables checkpointing
    checkpoint_root: str

    model: ModelConfig
    env: EnvConfig
    loader: LoaderConfig
    optim: OptimizerConfig
    scheduler: SchedulerConfig
    dataset: DatasetConfig

    sample_tokens: int = 0          # > 0: KV-cache sample after training
    sample_top_p: float = 0.0       # > 0: nucleus filter for sampling
    sample_temperature: float = 0.8
    eval_batches: int = 0           # > 0: validation-split ppl after training


def batch_sharding(mesh) -> NamedSharding:
    """Batch over the data axes, sequence over sp (GPT.batch_spec,
    filtered to the axes this mesh actually has)."""
    axes = mesh.axis_names
    data = tuple(a for a in ("dp", "fsdp") if a in axes) or None
    seq = "sp" if "sp" in axes else None
    return NamedSharding(mesh, P(data, seq))


def main(conf: Config) -> dict:
    rng = utils.seed(conf.seed)
    cfg = conf.model.make()
    mesh = dist.get_mesh(conf.env)

    dataset = conf.dataset.make(Split.TRAIN, seq_len=cfg.seq_len + 1,
                                vocab=cfg.vocab)
    loader = conf.loader.make(dataset, shuffle=True,
                              distributed=conf.env.distributed,
                              seed=conf.seed)

    def _loss(params, batch, dropout_rng):
        ids, labels = batch["ids"], batch["labels"]
        out, aux = GPT.apply(
            params, ids, cfg=cfg, mesh=mesh,
            compute_dtype=conf.env.compute_dtype(),
            remat=conf.model.remat, return_aux=True,
            return_hidden=conf.model.chunked_head,
            dropout_rng=dropout_rng)
        if conf.model.chunked_head:
            # stream tokens through the LM head so the (T, vocab)
            # logits never materialize (off in both train cells of the
            # benchmark, so it has no number on the current stack)
            loss = lm_head_cross_entropy(out, GPT.head_table(params),
                                         labels)
        else:
            loss = cross_entropy(out, labels)
        metrics = {"ppl": jax.numpy.exp(loss)}
        if cfg.n_experts:
            metrics["aux"] = aux
            loss = loss + conf.model.aux_weight * aux
        return loss, metrics

    def loss_fn(params, batch, rng):
        # make_step splits a fresh rng per step → per-step dropout masks
        # (identity when model.dropout is 0)
        return _loss(params, batch, rng)

    def eval_loss_fn(params, batch, rng):
        del rng                       # eval forward stays deterministic
        return _loss(params, batch, None)

    schedule = conf.scheduler.make(conf.optim)
    tx = conf.optim.make(schedule)
    state = utils.TrainState.create(
        GPT.init(rng, cfg), tx, rng=rng,
        accumulate=conf.accumulate_every > 1)
    # config front door: the YAML mesh line lays out the whole state by
    # the model's rule table (replaces DDP's replicate-everything)
    state = conf.env.make(state, model=GPT)

    # checkpoint + the resume half the reference lacked (SURVEY §5.4):
    # restoring `like=state` re-applies the mesh layout, so resume works
    # unchanged across mesh sizes
    save_cb = None
    start_iter = 0
    if conf.save_every:
        from torchbooster_tpu.callbacks import SaveCallback

        save_cb = SaveCallback(conf.save_every, conf.n_iter,
                               root=conf.checkpoint_root)
        restored = save_cb.restore(like={"state": state})
        if restored is not None:
            state = restored["state"]
            start_iter = int(np.asarray(state.step))
            if dist.is_primary():
                print(f"resumed from step {start_iter}")
    step = utils.make_step(loss_fn, tx, clip=conf.clip,
                           accumulate_every=conf.accumulate_every,
                           mesh=mesh)

    sharding = batch_sharding(mesh)

    def shard(tokens) -> dict:
        # pre-shift on host so ids/labels both shard cleanly over sp
        tokens = np.asarray(tokens)
        return {
            "ids": jax.device_put(
                np.ascontiguousarray(tokens[:, :-1]), sharding),
            "labels": jax.device_put(
                np.ascontiguousarray(tokens[:, 1:]), sharding),
        }

    metrics = MetricsAccumulator()
    results = {}
    batches = utils.iter_loader(loader)
    bar = tqdm(range(start_iter, conf.n_iter), desc="train",
               disable=not dist.is_primary())
    with mesh:
        for it in bar:
            epoch, tokens = next(batches)
            state, step_metrics = step(state, shard(tokens))
            metrics.update(step_metrics)
            if (it + 1) % conf.log_every == 0:
                results = {"iter": it + 1, "epoch": epoch,
                           **metrics.compute()}
                metrics.reset()
                if dist.is_primary():
                    bar.set_postfix({k: f"{v:.4f}" for k, v in
                                     results.items()
                                     if isinstance(v, float)})
            if save_cb is not None and (it + 1) % conf.save_every == 0:
                save_cb.save(it + 1, state=state)
    if save_cb is not None:
        save_cb.wait()
    if conf.eval_batches > 0:
        # held-out perplexity on the VALIDATION split (text_file keeps
        # it disjoint from train/test; synthetic_lm reseeds per split)
        eval_step = utils.make_eval_step(eval_loss_fn)
        eval_loader = conf.loader.make(
            conf.dataset.make(Split.VALIDATION, seq_len=cfg.seq_len + 1,
                              vocab=cfg.vocab),
            shuffle=False, distributed=conf.env.distributed,
            seed=conf.seed)
        eval_metrics = MetricsAccumulator()
        with mesh:
            for i, tokens in enumerate(eval_loader):
                if i >= conf.eval_batches:
                    break
                eval_metrics.update(
                    eval_step(state.params, shard(tokens), state.rng))
        evals = eval_metrics.compute()
        if not evals:
            if dist.is_primary():
                print("eval skipped: validation split yielded no full "
                      "batches (drop_last) — shrink batch_size or grow "
                      "the corpus")
        else:
            results["val_loss"] = evals["loss"]
            results["val_ppl"] = evals["ppl"]
            if dist.is_primary():
                print({"val_loss": round(evals["loss"], 4),
                       "val_ppl": round(evals["ppl"], 4)})
    if conf.sample_tokens > 0:
        # KV-cache decoding (models/gpt.py generate): prompt with the
        # first tokens of a training example, continue the sequence
        _, tokens = next(batches)
        prompt = np.asarray(tokens)[:1, :8].astype(np.int32)
        sampled = GPT.generate(
            state.params, prompt, cfg, n_new=conf.sample_tokens,
            rng=state.rng, temperature=conf.sample_temperature, top_k=50,
            top_p=conf.sample_top_p or None)
        results["sample"] = np.asarray(sampled)[0].tolist()
        if dist.is_primary():
            print("sample:", results["sample"])
            if cfg.vocab == 256:
                # byte-level corpus (dataset name: text_file) — the ids
                # ARE utf-8 bytes, show the text
                from torchbooster_tpu.data import ByteTokenizer

                print("sample text:", repr(
                    ByteTokenizer().decode(results["sample"])))
    if dist.is_primary():
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in results.items()})
    return results


if __name__ == "__main__":
    conf = Config.load("gpt.yml")
    utils.boost()
    dist.launch(main, conf.env.n_devices, conf.env.n_machine,
                conf.env.machine_rank, conf.env.dist_url, args=(conf,))
