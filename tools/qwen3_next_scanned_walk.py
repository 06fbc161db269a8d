"""What a walk SCANNED BY PERIODS over the Qwen3-Next layers would cost against
the unrolled one the program ships (`models/qwen3_next.Layers`), at the cell's
shapes on the chip: the same weights, the same engine, the same `generate` (8
rows of 32,768 tokens and 512 new), once with the layers as they are and once
with `Layers` replaced by `nn.scan` over ONE period (three Gated DeltaNet
layers and a full-attention layer, each with its experts) and the three
periods' parameters stacked.

    python tools/qwen3_next_scanned_walk.py                 # on the chip
    JAX_PLATFORMS=cpu python tools/qwen3_next_scanned_walk.py --rehearsal

A walk's line gives what JAX itself reports of making the program (tracing,
lowering, the backend's compile; the persistent compile cache is off, so both
are made from nothing), the seconds of the first call and of `--batches`
calls after it, and the share of the scanned walk's new tokens equal to the
unrolled walk's. ISSUE 64 asked for ONE measurement (set-up seconds against
`out_tok_s`); the program unrolls because under a scan the grouped expert
GEMM, a Pallas call a slice cannot fuse into, is handed a copy of the layer's
held experts every pass (PERF.md, PRs 51 and 64, have the readings). The
program has no such option: the scanned walk lives here alone."""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PARTS = ("layer_{i}", "layer_{i}_norm", "layer_{i}_mlp", "layer_{i}_mlp_norm")


def scanned_layers():
    """`Layers` as a scan over PERIODS: one period's code (`layer_0` ..
    `layer_<n-1>` by its place in the period), parameters stacked (periods,
    ...) under `periods`, the cache carried and each kind's slot named by
    the period's index."""
    import flax.linen as nn
    import jax.numpy as jnp
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import qwen3_next as qn

    class Period(nn.Module):
        cfg: qn.Qwen3NextConfig

        @nn.compact
        def __call__(self, carry, period, row):
            cfg = self.cfg
            n = cfg.full_attention_interval
            h, cache = carry
            state, kv = (None, None) if cache is None else (cache.state,
                                                            cache.kv)
            norm = lambda name: qn.OnePlusNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
            staged = None
            for j in range(n):
                x = norm(f"layer_{j}_norm")(h)
                if j < n - 1:
                    out, state = qn.GatedDeltaNet(cfg, name=f"layer_{j}")(
                        x, state, period * (n - 1) + j, row)
                else:
                    out, made = qn.GatedAttention(cfg, name=f"layer_{j}")(
                        x, kv, period, row)
                    if isinstance(made, tuple):
                        staged = made
                    else:
                        kv = made
                h = h + out
                h = h + hybrid.held_experts(
                    cfg, f"layer_{j}_mlp", held=cfg.num_experts,
                    activation="silu", score_fn="softmax",
                    shared=cfg.shared_expert_intermediate_size,
                    shared_gate=True)(norm(f"layer_{j}_mlp_norm")(h),
                                      train=False)
            if cache is not None:
                cache = cache.replace(state=state, kv=kv)
            return (h, cache), staged

    class Layers(nn.Module):
        cfg: qn.Qwen3NextConfig

        @nn.compact
        def __call__(self, h, cache=None, row=None):
            periods = self.cfg.num_hidden_layers \
                // self.cfg.full_attention_interval
            walk = nn.scan(Period, variable_axes={"params": 0, "counters": 0},
                           split_rngs={"params": True},
                           in_axes=(0, nn.broadcast), length=periods,
                           metadata_params={nn.meta.PARTITION_NAME: "layers"})
            (h, cache), staged = walk(self.cfg, name="periods")(
                (h, cache), jnp.arange(periods, dtype=jnp.int32), row)
            if staged is not None:
                cache = cache.replace(kv=cache.kv.land(*staged))
            return h, cache

    return Layers


def stacked(params, cfg):
    """The unrolled tree's layers stacked a period for the scanned walk."""
    import jax
    import jax.numpy as jnp
    layers, n = params["layers"], cfg.full_attention_interval
    periods = {part.format(i=j): jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(layers[part.format(i=p * n + j)]
          for p in range(cfg.num_hidden_layers // n)))
        for j in range(n) for part in _PARTS}
    return {**params, "layers": {"periods": periods}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=32768)
    ap.add_argument("--new", type=int, default=512)
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--seed", type=int, default=64)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import qwen3_next
    from deepspeed_tpu.utils import groups
    from perfbench.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    made = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: made.update({event: seconds}))

    manifest = Manifest()
    sizes = manifest.config("qwen3-next-80b-l12-ep8")
    if args.rehearsal:
        sizes = {**sizes, **sizes["rehearsal"]}
        args.batch, args.prompt, args.new = 2, 40, 6
    adapter = manifest.module("configs", sizes["adapter"])
    cfg = adapter.model_config(sizes, dtype=jnp.bfloat16)
    model = adapter.materialize(cfg, args.seed, jnp.bfloat16)[0]
    # the ONE draw, made anew a walk: an engine may re-lay what it is given
    weights = lambda: adapter.materialize(cfg, args.seed, jnp.bfloat16)[1]  # noqa: E731
    ids = [np.random.default_rng([args.seed, n]).integers(
        1, cfg.vocab_size, size=(args.batch, args.prompt)).astype(np.int32)
        for n in range(1 + args.batches)]

    def walk(name, params):
        groups.reset_topology()
        made.clear()
        engine = deepspeed_tpu.init_inference(
            model, params=params, dtype="bf16", tensor_parallel={"tp_size": 1})
        del params
        seconds, outs = [], []
        for batch in ids:
            t = time.perf_counter()
            outs.append(np.asarray(engine.generate(
                batch, max_new_tokens=args.new))[:, args.prompt:])
            seconds.append(time.perf_counter() - t)
        stage = lambda key: round(sum(  # noqa: E731
            s for event, s in made.items() if key in event), 3)
        line = {"walk": name, "trace_s": stage("jaxpr_trace"),
                "lower_s": stage("jaxpr_to_mlir"),
                "compile_s": stage("backend_compile"),
                "first_call_s": round(seconds[0], 3),
                "batch_s": [round(s, 3) for s in seconds[1:]]}
        return line, outs

    lines = []
    line, want = walk("unrolled", weights())
    lines.append(line)
    print(json.dumps(line), flush=True)
    tree = stacked(weights(), cfg)
    unrolled, qwen3_next.Layers = qwen3_next.Layers, scanned_layers()
    try:
        line, got = walk("scanned", tree)
    finally:
        qwen3_next.Layers = unrolled
    line["tokens_equal"] = float(np.mean(
        [np.mean(a == b) for a, b in zip(got, want)]))
    lines.append(line)
    print(json.dumps(line), flush=True)
    print(json.dumps({"device": jax.devices()[0].platform,
                      "batch": args.batch, "prompt": args.prompt,
                      "new": args.new, "walks": lines}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
