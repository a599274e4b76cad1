"""The system under test: the program's federated round, built as its own
trainer builds it, for one cell.

``driver: "scan"`` is the one-chip round of ``launch/train.train``:
``core/fed.make_fl_round`` with the ``scan`` client mode, compiled once
for the cell's batch shape.  ``sparsify_backend`` picks the threshold
mask's implementation.  This module and ``run.py`` are the only files of
the benchmark that import the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch_config(c: dict, check: bool = True):
    """The program's ``ArchConfig`` for configuration file ``c``: its
    registry entry with the file's sizes.  ``check`` asserts that only
    the keys the file lists as ``reduced`` or ``program_overrides`` (where
    the file follows the published model against the registry) differ."""
    from repro.configs import get_config
    from repro.configs.base import AttentionSpec, EncoderSpec, LayerSpec

    base = get_config(c["program_arch"])
    attn = AttentionSpec(num_heads=c["num_attention_heads"],
                         num_kv_heads=c["num_key_value_heads"],
                         head_dim=c["head_dim"], rope_theta=c["rope_theta"])
    layer = LayerSpec(kind="attn", attention=attn,
                      d_ff=c["intermediate_size"], gated_mlp=c["gated_mlp"])
    enc = EncoderSpec(num_layers=c["encoder_layers"],
                      num_heads=c["num_attention_heads"],
                      src_len=c["encoder_frames"]) \
        if c["encoder_layers"] else None
    cfg = dataclasses.replace(
        base, d_model=c["hidden_size"], vocab_size=c["vocab_size"],
        layer_pattern=(layer,), pattern_repeats=c["num_hidden_layers"],
        encoder=enc, norm_eps=c["norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["dtype"])
    if check:
        fields = {"num_hidden_layers": "pattern_repeats",
                  "tie_word_embeddings": "tie_embeddings"}
        keep = {v: getattr(base, v) for k, v in fields.items()
                if k in c["reduced"] or k in c.get("program_overrides", {})}
        if dataclasses.replace(cfg, **keep) != base:
            raise ValueError(
                f"{c['name']}: the file's sizes differ from the program's "
                f"{c['program_arch']} beyond {c['reduced']} and "
                f"{list(c.get('program_overrides', {}))}")
    return cfg


def fed_config(mix: dict):
    from repro.core import FedConfig
    from repro.optim import AdamHyper
    return FedConfig(
        algorithm=mix["algorithm"], alpha=mix["alpha"],
        local_epochs=mix["local_epochs"], n_clients=mix["clients"],
        adam=AdamHyper(lr=mix["lr"]), client_mode="scan",
        exact_topk=mix["exact_topk"],
        sparsify_backend=mix["sparsify_backend"])


class Program:
    """The compiled round with its state, built once and driven by both
    the first (checked) rounds and the measured window."""

    def __init__(self, c: dict, mix: dict, seed: int, check: bool = True):
        from repro.core import fed as fed_mod
        from repro.models import init_params, loss_fn

        if mix["driver"] != "scan":
            raise ValueError(f"no driver {mix['driver']!r} here")
        self.cfg = cfg = arch_config(c, check)
        self.fed = fed = fed_config(mix)
        self.key = jax.random.PRNGKey(seed)
        remat = mix["remat"]

        def loss(p, batch):
            return loss_fn(cfg, p, batch["tokens"],
                           frontend_embeds=batch.get("embeds"), remat=remat)

        self._init = jax.jit(lambda k: init_params(cfg, k))
        self.state = fed_mod.fed_init(fed, self._init(self.key))
        self.leaf_names = [jax.tree_util.keystr(k) for k, _ in
                           jax.tree_util.tree_flatten_with_path(
                               self.state.W)[0]]
        self.round_jit = jax.jit(fed_mod.make_fl_round(fed, loss))
        self.active = fed_mod.active_client_count(fed)

    def compile(self, batch):
        self.step = self.round_jit.lower(self.state, batch).compile()
        self._norms = jax.jit(_leaf_norms)
        self._change = jax.jit(lambda W, W0: _leaf_norms(jax.tree.map(
            lambda a, b: a.astype(F32) - b.astype(F32), W, W0)))

    def run_round(self, batch):
        self.state, mets = self.step(self.state, batch)
        return mets

    def m_norms(self):
        return [float(x) for x in jax.device_get(self._norms(self.state.M))]

    def change_norms(self):
        # the initial weights come from the same compiled ``_init`` that
        # made the state: traced into another program, the TPU compiler
        # drops the bf16 rounding of the initialisation (excess precision)
        return [float(x) for x in jax.device_get(
            self._change(self.state.W, self._init(self.key)))]

    def hlo_text(self) -> str:
        return self.step.as_text()


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])
