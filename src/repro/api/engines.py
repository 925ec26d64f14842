"""Built-in engine registrations.

Importing this module (done lazily by the registry) registers the paper's
engines: ``mesp`` (§4, production scan form), ``mesp_seq`` (§4.3 sequential
loop with immediate optimizer updates), ``mesp_pallas`` (§4 fused into
Pallas TPU kernels), ``mebp`` (§3.3 autodiff baseline), ``store_h``
(Table 5 ablation), and — via ``repro.zo.engines`` — the zeroth-order
family: ``mezo`` (§3.2 baseline) plus the structured-sampler variants
``mezo_sparse`` / ``mezo_lowrank`` / ``mezo_block`` / ``mezo_avg4``.
"""
from __future__ import annotations

from repro.api.registry import register_engine


def _grad_builder(spec, cfg, opt, policy):
    """Shared step-builder for engines that are `mesp.value_and_grad` under
    a specific ExecutionPolicy backend. A model with counters
    (``model.aux_names``: the ``moe`` family's expert counters) gets a step
    that returns them fourth, to be read with the loss."""
    from repro.core import mesp
    from repro.models import model as model_lib

    fn = (mesp.value_grad_and_aux if model_lib.aux_names(cfg)
          else mesp.value_and_grad)

    def vag(params, batch, policy=policy):
        return fn(params, cfg, batch, policy=policy)

    if policy.backend == "pallas":
        vag = _kernel_data_parallel(vag, cfg, policy)

    def step(params, opt_state, batch):
        loss, grads, *aux = vag(params, batch)
        params, opt_state = opt.update(grads, opt_state, params)
        return (params, opt_state, loss, *aux)

    return step


def _kernel_data_parallel(vag, cfg, policy):
    """Data parallelism for the Pallas backend. XLA cannot partition a
    Mosaic kernel, so under a mesh whose ``model`` axis is 1 the
    value-and-grad runs per batch shard inside a ``shard_map`` and the loss
    and grads are averaged over the data axes (the gradient all-reduce).

    The batch layout is the Trainer's: ``policy.act_spec[0]`` names the
    data axes it splits the batch over, or is None when every device holds
    the whole batch. Without an ``act_spec`` (no mesh) or under model
    parallelism (which the kernels do not support on a TPU) ``vag`` runs
    as is."""
    if policy.act_spec is None:
        return vag

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.models.layers import ambient_mesh

    bdim = policy.act_spec[0]
    # activation constraints name mesh axes, which are manual in the body
    local_policy = policy.with_(act_spec=None)

    def run(params, batch):
        mesh = ambient_mesh()
        if mesh is None or mesh.shape.get("model", 1) != 1:
            return vag(params, batch)

        def shard(params, batch):
            out = vag(params, batch, policy=local_policy)
            if bdim is None:
                return out
            # loss and grads averaged over the data shards, counters summed
            return (*jax.lax.pmean(out[:2], bdim),
                    *jax.lax.psum(out[2:], bdim))

        # check_vma off: pallas_call outputs carry no varying-axes info;
        # every output here is replicated (one spec, a prefix of them all)
        return jax.shard_map(shard, mesh=mesh, in_specs=(P(), P(bdim)),
                             out_specs=P(), check_vma=False)(params, batch)

    return run


def _grad_vag(params, cfg, batch, *, policy, key=None):
    from repro.core import mesp
    return mesp.value_and_grad(params, cfg, batch, policy=policy)


register_engine(
    "mesp", backend="structured", memsim="mesp", paper="§4",
    value_and_grad=_grad_vag,
    description="MeSP: hand-derived structured backward (h recomputed), "
                "scan-over-blocks form")(_grad_builder)

register_engine(
    "mesp_pallas", backend="pallas", memsim="mesp", paper="§4 + kernels",
    value_and_grad=_grad_vag,
    # AOT-lowering interpret-mode Pallas kernels for the 0.5B–3B paper
    # models is not meaningful off-TPU; benchmarks/kernels.py covers this
    # engine's perf trajectory instead.
    benchmark=False,
    description="MeSP with the structured rules fused into Pallas TPU "
                "kernels: sparse-grid flash attention (causal/window tiles "
                "skipped at trace time), optional in-kernel RoPE "
                "(--fuse-rope); interpret mode off-TPU")(_grad_builder)

register_engine(
    "mebp", backend="plain", memsim="mebp", paper="§3.3",
    value_and_grad=_grad_vag,
    description="MeBP baseline: per-block checkpointing + framework "
                "autodiff")(_grad_builder)

register_engine(
    "store_h", backend="store_h", memsim="store_h", paper="Table 5",
    value_and_grad=_grad_vag,
    description="MeSP ablation: h = x@A stored instead of recomputed")(
    _grad_builder)


@register_engine(
    "mesp_seq", backend="structured", memsim="mesp", paper="§4.3",
    value_and_grad=_grad_vag,
    description="MeSP, paper §4.3 verbatim: reverse Python loop over "
                "blocks, SGD applied immediately per block (dense family)")
def _mesp_seq_builder(spec, cfg, opt, policy):
    from repro.core import mesp

    if cfg.family != "dense" or cfg.window_pattern:
        raise ValueError(
            "engine mesp_seq (paper §4.3) supports dense, non-patterned "
            f"architectures only — got family={cfg.family!r}")
    if spec.optimizer != "sgd":
        raise ValueError(
            "engine mesp_seq applies immediate per-block SGD (paper §4.3); "
            f"--optimizer {spec.optimizer!r} is not representable")
    lr = spec.lr

    def step(params, opt_state, batch):
        params, loss = mesp.sequential_train_step(params, cfg, batch, lr,
                                                  policy=policy)
        return params, {**opt_state, "step": opt_state["step"] + 1}, loss

    return step


# Zeroth-order engines (mezo + the structured variants) are registered by
# the pluggable ZO subsystem — one engine per sampler × query combination.
from repro.zo import engines as _zo_engines  # noqa: E402,F401  (self-registers)
