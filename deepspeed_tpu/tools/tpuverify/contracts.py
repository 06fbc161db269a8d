"""The eight program-level contracts (docs/static_analysis.md, semantic
layer). Each one is a perf-ledger incident turned into an executable
claim; the ``incident`` string is the provenance the docs catalog renders.
"""

from __future__ import annotations

from typing import Iterable

from deepspeed_tpu.tools.tpuverify.core import Contract, Violation, register
from deepspeed_tpu.tools.tpuverify.jaxpr_util import (
    CALLBACK_PRIMS,
    SHARD_MAP_PRIMS,
    aliasing_output_count,
    arg_aliasing,
    count_cache_scatters,
    donated_leaves,
    primitive_eqns,
)

# Scatter discipline only polices real KV payloads: cache data (float /
# bf16), int8-at-rest pools, and their f32 scales. int32 leaves (block
# tables, cursors) update with cheap small writes that can collide in
# shape with unrelated buffers (output-token scatters are int32 too).
_KV_DTYPE_PREFIXES = ("float", "bfloat", "int8")


def _kv_shapes(cache_shapes) -> set:
    return {(s, d) for s, d in cache_shapes
            if d.startswith(_KV_DTYPE_PREFIXES)}


@register
class DonationAliasing(Contract):
    id = "donation-aliasing"
    doc = ("Train-step and v2 serving programs must donate their "
           "TrainState/KV-cache argument buffers, and the donation must "
           "survive into the lowered program's input-output aliasing.")
    incident = ("r5: the 7B serving bring-up OOMed at 2x weight residency "
                "because a stale params reference kept the old tree alive "
                "through re-placement — undonated/unaliased buffers are "
                "exactly that class, one jit spec away.")

    def applies(self, put) -> bool:
        return put.kind == "program" and bool(put.donate)

    def check(self, put) -> Iterable[Violation]:
        lowered = put.lowered()
        if lowered is None:
            return  # non-lowerable callable (auto-layout lambda) — skip
        for argnum in put.donate:
            try:
                donated, total = donated_leaves(lowered, argnum)
            except (IndexError, TypeError):
                yield Violation(self.id, put.name,
                                f"arg {argnum} missing from the lowered "
                                "program's args_info — donation spec and "
                                "call signature have drifted")
                continue
            if total and donated < total:
                yield Violation(
                    self.id, put.name,
                    f"arg {argnum}: {total - donated}/{total} buffer(s) "
                    "not donated — the old buffer stays live across the "
                    "step (2x residency)")
        n_aliased = aliasing_output_count(lowered)
        if n_aliased == 0:
            yield Violation(
                self.id, put.name,
                "no input-output aliasing in the lowered program "
                "(tf.aliasing_output absent) — donation never reached "
                "the compiler")


@register
class PinnedShardingCoverage(Contract):
    id = "pinned-sharding"
    doc = ("Every param/cache leaf an engine feeds its pinned serving "
           "programs must carry a committed NamedSharding; bulk leaves "
           "observed entering a pinned program must have been committed.")
    incident = ("r4: unpinned v2 cache leaves silently recompiled every "
                "serving program (~3.5 s each) on each admission wave — "
                "uncommitted leaves re-key the jit cache.")

    def applies(self, put) -> bool:
        return put.kind == "engine"

    def check(self, put) -> Iterable[Violation]:
        import jax
        from jax.sharding import NamedSharding
        import numpy as np

        for label, tree in put.pinned_trees:
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            for path, leaf in flat:
                if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
                    continue
                sh = getattr(leaf, "sharding", None)
                committed = bool(getattr(leaf, "_committed", False))
                if isinstance(sh, NamedSharding) and committed:
                    continue
                where = f"{label}{jax.tree_util.keystr(path)}"
                why = ("uncommitted placement"
                       if not committed else
                       f"sharding is {type(sh).__name__}, not NamedSharding")
                yield Violation(
                    self.id, put.name,
                    f"{where}: {why} — this leaf re-keys the pinned "
                    "serving programs (silent recompile per dispatch)")
        if not put.check_signatures:
            return
        for program, sig in getattr(put.detector, "signatures", {}).items():
            for i, entry in enumerate(sig):
                shape = entry.get("shape")
                if shape is None:
                    continue
                try:
                    nbytes = int(np.prod(shape, dtype=np.int64)) * \
                        np.dtype(entry.get("dtype", "f4")).itemsize
                except TypeError:
                    continue
                if nbytes < put.bulk_bytes:
                    continue  # per-call ids/rng — not part of the contract
                if not entry.get("committed"):
                    yield Violation(
                        self.id, put.name,
                        f"program {program!r}: bulk input leaf #{i} "
                        f"(shape {shape}, {nbytes} B) entered uncommitted "
                        "— its placement re-keys the program")


@register
class KVScatterDiscipline(Contract):
    id = "kv-scatter-discipline"
    doc = ("At most one batched scatter per KV collection (K and V each) "
           "per program body: decode stages its token and apply_stage "
           "lands every layer in one batched scatter; flush is one "
           "fixed-shape drop-scatter.")
    incident = ("r4: per-length eager cache scatters compiled ~1.5 s "
                "APIECE and the unstaged token scatter cost ~0.3 ms per "
                "layer per step — 2L scatters/step dominated decode.")

    def applies(self, put) -> bool:
        return put.kind == "program" and bool(put.cache_shapes)

    def check(self, put) -> Iterable[Violation]:
        targets = _kv_shapes(put.cache_shapes)
        if not targets:
            return
        counts = count_cache_scatters(put.jaxpr(), targets)
        for (path, (shape, dtype)), n in sorted(counts.items()):
            if n > put.scatter_budget:
                yield Violation(
                    self.id, put.name,
                    f"{n} scatters into cache aval {shape} {dtype} in one "
                    f"program body (budget {put.scatter_budget}; body "
                    f"{path}) — stage appends and land them with one "
                    "batched scatter per step")


# MLIR element types of the dtypes a KV pool or its scales can have
_MLIR_ELT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
             "int8": "i8"}


@register
class KVPoolInPlace(Contract):
    id = "kv-pool-in-place"
    doc = ("A v2 serving program holds ONE buffer per paged KV pool from "
           "argument to result: no scan slices a pool per layer (a scanned "
           "input or output of the pool's shape), no dynamic_slice or "
           "dynamic_update_slice takes a pool, and the lowered module "
           "aliases every pool argument to an output. Layers address the "
           "stacked pool by index. A v1 generate program holds ONE buffer "
           "per stacked dense cache tensor through its token loop: no scan "
           "there scans over the stack or a layer of it, no dynamic_slice, "
           "dynamic_update_slice or transpose takes either, and a kernel "
           "that returns a stack aliases it to the stack it was given.")
    incident = ("PR 29: the cached block scan scanned over the stacked "
                "pools, so each of a round's two passes cut every layer's "
                "pool out and wrote it back, and the chunk scatter copied "
                "it once more: 36 ms of an 80 ms round at Qwen2.5-3B, and a "
                "second copy of the pool in every chunk program. PR 42: "
                "the dense cache of v1 generate, scanned over per layer "
                "inside the token loop and re-laid for the kernel: 3.1 ms "
                "of a 17 ms decode step at Qwen2.5-3B, 32 rows.")

    def applies(self, put) -> bool:
        return put.kind == "program" and bool(put.pool_shapes
                                              or put.stack_shapes)

    def check(self, put) -> Iterable[Violation]:
        if put.stack_shapes:
            yield from self._check_token_loop(put)
        if put.pool_shapes:
            yield from self._check_pools(put)

    def _check_token_loop(self, put) -> Iterable[Violation]:
        """The stacked dense cache inside a v1 program's token loop."""
        stacks = set(put.stack_shapes)
        # a layer of the stack, in the stack's order or the per-layer
        # view's (..., M, Hkv, D): what a scan over it cuts out
        layers = {(s[1:], d) for s, d in stacks}
        layers |= {(s[:-3] + (s[-2], s[-3], s[-1]), d) for s, d in layers}
        cached = stacks | layers

        def like(var, shapes) -> bool:
            aval = getattr(var, "aval", None)
            return aval is not None and \
                (tuple(aval.shape), str(aval.dtype)) in shapes

        loops = [eqn for _, eqn in primitive_eqns(put.jaxpr(), {"scan"})
                 if eqn.params["length"] == put.token_loop
                 and any(like(v, stacks) for v in eqn.invars)]
        if not loops:
            yield Violation(
                self.id, put.name,
                f"no scan of length {put.token_loop} holds a stacked dense "
                f"cache {sorted(stacks)}: the contract has nothing to hold")
        for loop in loops:
            body = loop.params["jaxpr"]
            for path, eqn in primitive_eqns(body, {"scan"}):
                skip = eqn.params["num_consts"] + eqn.params["num_carry"]
                sliced = [v for v in eqn.invars[skip:] if like(v, stacks)] + \
                    [v for v in eqn.outvars[eqn.params["num_carry"]:]
                     if like(v, stacks)]
                if sliced:
                    yield Violation(
                        self.id, put.name,
                        f"scan in the token loop ({path}) scans over "
                        f"{len(sliced)} cache-shaped operand(s) "
                        f"{tuple(sliced[0].aval.shape)}: every layer's K/V "
                        "is cut out of the stack and written back each "
                        "step — close over the stack and scan the layer "
                        "index (kv_cache.scan_dense_layers)")
            for path, eqn in primitive_eqns(
                    body, {"dynamic_slice", "dynamic_update_slice",
                           "transpose"}):
                if like(eqn.invars[0], cached):
                    yield Violation(
                        self.id, put.name,
                        f"{eqn.primitive.name} of a cache-shaped value "
                        f"{tuple(eqn.invars[0].aval.shape)} in the token "
                        f"loop ({path}) — the decode kernel reads the "
                        "stack by layer index and a step writes one token "
                        "a row (KVCache.land)")
            for path, eqn in primitive_eqns(body, {"pallas_call"}):
                aliased = {o for _, o in
                           eqn.params.get("input_output_aliases", ())}
                for i, out in enumerate(eqn.outvars):
                    if like(out, stacks) and i not in aliased:
                        yield Violation(
                            self.id, put.name,
                            f"pallas_call in the token loop ({path}) "
                            f"returns a stack {tuple(out.aval.shape)} it "
                            "does not alias to an input — the step holds "
                            "a second copy of the cache")

    def _check_pools(self, put) -> Iterable[Violation]:
        pools = set(put.pool_shapes)

        def is_pool(var) -> bool:
            aval = getattr(var, "aval", None)
            return aval is not None and \
                (tuple(aval.shape), str(aval.dtype)) in pools

        for path, eqn in primitive_eqns(put.jaxpr(), {"scan"}):
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            sliced = [v for v in eqn.invars[skip:] if is_pool(v)] + \
                [v for v in eqn.outvars[eqn.params["num_carry"]:]
                 if is_pool(v)]
            if sliced:
                yield Violation(
                    self.id, put.name,
                    f"scan in {path} scans over {len(sliced)} pool-shaped "
                    f"operand(s) {tuple(sliced[0].aval.shape)}: every layer's "
                    "pool is cut out of the stack and written back — carry "
                    "or close over the pool and scan the layer index "
                    "(kv_cache.scan_paged_layers)")
        for path, eqn in primitive_eqns(
                put.jaxpr(), {"dynamic_slice", "dynamic_update_slice"}):
            if is_pool(eqn.invars[0]):
                yield Violation(
                    self.id, put.name,
                    f"{eqn.primitive.name} of a pool "
                    f"{tuple(eqn.invars[0].aval.shape)} in {path} — address "
                    "the stacked pool by (layer, block) index instead")
        lowered = put.lowered()
        if lowered is None:
            return
        args = arg_aliasing(lowered)
        for shape, dtype in sorted(pools):
            mine = [a for a in args
                    if a[0] == shape and a[1] == _MLIR_ELT.get(dtype)]
            loose = sum(1 for a in mine if not a[2])
            if not mine or loose:
                yield Violation(
                    self.id, put.name,
                    f"{loose or 'every'} pool argument(s) {shape} {dtype} "
                    "not aliased to an output in the lowered module — the "
                    "program holds a second copy of the pool")


@register
class NoHostCallback(Contract):
    id = "no-host-callback"
    doc = ("No pure_callback/io_callback/debug-print primitives in "
           "hot-path programs — a callback is a device→host→device round "
           "trip per step.")
    incident = ("r9: fault-injection points are HOST-only by design; this "
                "is the semantic backstop for tpulint's "
                "host-only-fault-points rule — it catches indirection the "
                "traced-function index misses.")

    def applies(self, put) -> bool:
        return put.kind == "program" and put.check_callbacks

    def check(self, put) -> Iterable[Violation]:
        for path, eqn in primitive_eqns(put.jaxpr(), CALLBACK_PRIMS):
            yield Violation(
                self.id, put.name,
                f"host-escape primitive {eqn.primitive.name!r} in traced "
                f"body {path} — every capability must be a property of "
                "the compiled step, not a host round trip inside it")


@register
class ManualRegionAllowlist(Contract):
    id = "manual-region-allowlist"
    doc = ("shard_map manual regions appear only where the wire format "
           "matters (pipeline rotation, ZeRO++ collectives, ring "
           "attention, ops/pallas/sharded.py wrappers) — everything else "
           "stays GSPMD auto.")
    incident = ("Architecture invariant since r1; manual regions outside "
                "the allowlist forfeit GSPMD propagation and, on the old-"
                "jaxlib sandboxes, are the programs XLA:CPU SIGABRTs on.")

    def applies(self, put) -> bool:
        return put.kind == "program"

    def check(self, put) -> Iterable[Violation]:
        if put.allow_shard_map:
            return
        for path, eqn in primitive_eqns(put.jaxpr(), SHARD_MAP_PRIMS):
            yield Violation(
                self.id, put.name,
                f"shard_map manual region in body {path} of a program "
                "outside the wire-format allowlist — use GSPMD auto "
                "sharding (or allowlist the program explicitly)")


@register
class RegistrationCoverage(Contract):
    id = "registration-coverage"
    doc = ("After a smoke dispatch, every compiled program in the engine "
           "caches has a RecompileDetector identity and was observed "
           "under it at dispatch — no untracked programs.")
    incident = ("r4: the unpinned-cache-leaf recompiles (~3.5 s each) were "
                "caught only where the detector watched; a program it "
                "never saw recompiles with no warning, no `recompile` "
                "event and no name on its `compile` span.")

    def applies(self, put) -> bool:
        return put.kind == "engine"

    def check(self, put) -> Iterable[Violation]:
        seen = getattr(put.detector, "_seen", {})
        for rec in put.records:
            if rec.detector_name is None:
                yield Violation(
                    self.id, put.name,
                    f"{rec.label}: compiled program has no "
                    "RecompileDetector identity — its recompiles are "
                    "invisible")
                continue
            if rec.detector_name not in seen:
                yield Violation(
                    self.id, put.name,
                    f"{rec.label}: program {rec.detector_name!r} was "
                    "never observed by the RecompileDetector at dispatch")


@register
class ResidencyCoverage(Contract):
    id = "residency-coverage"
    doc = ("After a smoke dispatch, the engine reports nonzero MemoryPlane "
           "bytes for params (every engine) and kv_cache (serving "
           "engines) — placement paths that skip registration make the "
           "residency ledger silently under-count.")
    incident = ("r6: the int8 7B tree measured 7.63 GB against a "
                "hand-derived 7.10 GB and the mismatch took a round to "
                "localize; unregistered placements are exactly the bytes "
                "such audits can never see.")

    def applies(self, put) -> bool:
        return put.kind == "engine"

    def check(self, put) -> Iterable[Violation]:
        res = getattr(put, "residency", None) or {}
        if res.get("params", 0) <= 0:
            yield Violation(
                self.id, put.name,
                "no registered params bytes after placement — the "
                "placement path bypassed MemoryPlane.register")
        if put.name != "train" and res.get("kv_cache", 0) <= 0:
            yield Violation(
                self.id, put.name,
                "no registered kv_cache bytes after a smoke dispatch — "
                "the cache build/dispatch path bypassed MemoryPlane")
