"""What a SCANNED walk over the Keye-sparse layers would cost against the
unrolled one the program ships (`models/keye_sparse.Layers`), at the cell's
shapes on the chip: the same weights, the same engine, the same `generate`
(8 rows of 32,768 tokens and 512 new), once with the layers as they are and
once with `Layers` replaced by `nn.scan` over ONE block and the twelve
layers' parameters stacked.

    python tools/keye_scanned_walk.py                       # on the chip
    JAX_PLATFORMS=cpu python tools/keye_scanned_walk.py --rehearsal

A walk's line gives what JAX itself reports of making the program (tracing,
lowering, the backend's compile; the persistent compile cache is off, so both
are made from nothing), the seconds of the first call and of `--batches`
calls after it, and the share of the scanned walk's new tokens equal to the
unrolled walk's. ISSUE 51 asked for the scanned walk (PR 43 priced an
unrolled one at 35 s of set-up); the program unrolls because under a scan the
grouped expert GEMM, a Pallas call a slice cannot fuse into, is handed a copy
of the layer's held experts every pass (PERF.md, PR 51, has both readings).
The program has no such option: the scanned walk lives here alone."""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PARTS = {"layer_{i}": "attn", "layer_{i}_norm": "attn_norm",
          "layer_{i}_mlp": "mlp", "layer_{i}_mlp_norm": "mlp_norm"}


def scanned_layers():
    """`Layers` as a scan: one block's code, parameters stacked (L, ...)
    under `blocks`, the cache carried and the layer named by index."""
    import flax.linen as nn
    import jax.numpy as jnp
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import keye_sparse as ks
    from deepspeed_tpu.models.llama import RMSNorm

    class Block(nn.Module):
        cfg: ks.KeyeSparseConfig

        @nn.compact
        def __call__(self, carry, slot, row):
            cfg = self.cfg
            h, cache = carry
            norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)  # noqa: E731
            out, made = ks.SparseAttention(cfg, name="attn")(
                norm("attn_norm")(h), cache, slot, row)
            staged = made if isinstance(made, tuple) else None
            if made is not None and staged is None:
                cache = made
            h = h + out
            h = h + hybrid.held_experts(
                cfg, "mlp", held=cfg.num_experts, activation="silu",
                score_fn="softmax")(norm("mlp_norm")(h), train=False)
            return (h, cache), staged

    class Layers(nn.Module):
        cfg: ks.KeyeSparseConfig

        @nn.compact
        def __call__(self, h, cache=None, row=None):
            depth = self.cfg.num_hidden_layers
            walk = nn.scan(Block, variable_axes={"params": 0, "counters": 0},
                           split_rngs={"params": True},
                           in_axes=(0, nn.broadcast), length=depth,
                           metadata_params={nn.meta.PARTITION_NAME: "layers"})
            (h, cache), staged = walk(self.cfg, name="blocks")(
                (h, cache), jnp.arange(depth, dtype=jnp.int32), row)
            if staged is not None:
                k, v, k_i = staged
                cache = cache.replace(kv=cache.kv.land(k, v),
                                      index_keys=cache.index_keys.land(k_i))
            return h, cache

    return Layers


def stacked(params, depth: int):
    """The unrolled tree's layers stacked for the scanned walk."""
    import jax
    import jax.numpy as jnp
    layers = params["layers"]
    blocks = {new: jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(layers[old.format(i=i)] for i in range(depth)))
        for old, new in _PARTS.items()}
    return {**params, "layers": {"blocks": blocks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=32768)
    ap.add_argument("--new", type=int, default=512)
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import keye_sparse
    from deepspeed_tpu.utils import groups
    from perfbench.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    made = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: made.update({event: seconds}))

    manifest = Manifest()
    sizes = manifest.config("keye-vl2-30b-l12-ep8")
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
    tree = stacked(weights(), cfg.num_hidden_layers)
    unrolled, keye_sparse.Layers = keye_sparse.Layers, scanned_layers()
    try:
        line, got = walk("scanned", tree)
    finally:
        keye_sparse.Layers = unrolled
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
