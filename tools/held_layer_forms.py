#!/usr/bin/env python3
"""A held expert layer's forms, each timed INSIDE a jitted whole layer that
makes its operands as the real one does (router logits, route, dispatch, the
three grouped GEMMs, combine), at the prefill shapes of the cells that run
one; device ms a call by the trace, by scope.

A probe of a kernel alone measures one fusion decision (PERF.md, PR 59), so
every form here is the whole layer. Forms:

  tree      `route_topk` + `held_dispatch_gmm` as the tree has them, the row
            bound by the layer's own rule
  pr59      the forms PR 59 shipped, kept HERE for the comparison: the
            router's `take_along_axis`, `bincount` for the counts, a
            fill-mode row gather, and the narrow body's way back as a second
            sort, three doubling passes and a row gather
  wide      the tree's full-width body alone (no bound)
  narrow    the tree's narrow body with the bound FORCED to twice the share's
            expected rows where the rule sets none (Ling's quarter)

    JAX_PLATFORMS=cpu python tools/held_layer_forms.py --rehearsal
    chiprun --timeout 1500 -- python tools/held_layer_forms.py

`--plans tt,rows[,dblk];...` times `tree` under other plans of the
`held_combine` kernel than its own. One JSON line a reading; prints no time
off the chip.
"""

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name: tokens, top k, hidden, expert width, held, scored, score, selection
# bias, groups, groups kept, the router's logits' spread (its cell serves the
# router's weights at that many times their seeded range): the prefill call
# of the cell named beside it
SHAPES = {
    "trinity": (16384, 8, 2048, 1024, 16, 128, "sigmoid", True, 1, 1, 4.0),
    "deepseek": (2048, 8, 7168, 2048, 16, 256, "sigmoid", True, 8, 4, 2.0),
    "openpangu": (2048, 8, 7680, 2048, 16, 256, "sigmoid", False, 1, 1, 1.0),
    "keye": (2048, 8, 2048, 768, 16, 128, "softmax", False, 1, 1, 4.4),
    "ling": (8192, 8, 2560, 768, 128, 512, "sigmoid", True, 8, 4, 1.0),
}
TOY = {"toy": (288, 4, 64, 32, 2, 16, "sigmoid", True, 1, 1, 4.0),
       "toy_quarter": (288, 4, 64, 32, 4, 16, "softmax", False, 1, 1, 1.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, same control flow")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--forms", default="tree,pr59,wide,narrow")
    ap.add_argument("--plans", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.ops.pallas import held_combine as hc
    from deepspeed_tpu.ops.pallas.grouped_gemm import grouped_gemm
    from deepspeed_tpu.telemetry.program_map import join_logdir, scope_tables

    F32 = jnp.float32
    shapes = TOY if args.rehearsal else SHAPES
    names = [n for n in args.shapes.split(",") if n] or list(shapes)
    reps = 2 if args.rehearsal else 10
    device = jax.devices()[0].platform

    def say(**line):
        print(json.dumps({"device": device, **line}), flush=True)

    def operands(shape, key):
        t, k, d, f, count, scored = shape[:6]
        ks = jax.random.split(key, 6)
        bf = jnp.bfloat16

        def normal(key, dims, scale=1.0):
            return (jax.random.normal(key, dims, F32) * scale).astype(bf)
        return (normal(ks[0], (t, d)),
                normal(ks[1], (d, scored), shape[10] * d ** -0.5),
                jax.random.normal(ks[2], (scored,), F32) * 0.01,
                normal(ks[3], (count, d, f), 0.02),
                normal(ks[4], (count, d, f), 0.02),
                normal(ks[5], (count, f, d), 0.02))

    def pr59_route(logits, k, score_fn, bias, n_group, topk_group):
        scores, chosen_by = sm.route_scores(logits, score_fn, bias, n_group,
                                            topk_group)
        _, idx = jax.lax.top_k(chosen_by, k)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
        return gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-20), idx

    def pr59_dispatch(x, gate_k, idx, count, grouped, bound):
        t, d = x.shape
        k = idx.shape[1]
        with jax.named_scope("dispatch"):
            held, local = sm.held_assignments(idx, 0, count)
            key = local.reshape(-1)
            order = jnp.argsort(key)
            sizes = jnp.bincount(key, length=count + 1)[:count]
            n_held = jnp.sum(sizes)

        def wide():
            with jax.named_scope("dispatch"):
                xs = jnp.take(x, order // k, axis=0)
            out_s = grouped(xs, sizes)
            with jax.named_scope("combine"):
                rows = jax.lax.broadcasted_iota(jnp.int32, (t * k, 1), 0)
                out_k = jnp.take(jnp.where(rows < n_held, out_s, 0),
                                 jnp.argsort(order), axis=0).reshape(t, k, d)
                return jnp.einsum("tk,tkd->td", jnp.where(held, gate_k, 0.0),
                                  out_k.astype(F32))

        def narrow():
            with jax.named_scope("dispatch"):
                first = order[:bound]
                xs = jnp.take(x, first // k, axis=0)
            out_s = grouped(xs, sizes)
            with jax.named_scope("combine"):
                tok = jnp.where(jnp.arange(bound) < n_held, first // k, t)
                by_tok = jnp.argsort(tok)
                tok = jnp.take(tok, by_tok)
                w = jnp.take(gate_k.reshape(-1), jnp.take(first, by_tok))
                terms = jnp.where(
                    (tok < t)[:, None],
                    jnp.take(out_s, by_tok, axis=0).astype(F32) * w[:, None],
                    0.0)
                step = 1
                while step < k:
                    same = jnp.concatenate([jnp.zeros((step,), jnp.bool_),
                                            tok[step:] == tok[:-step]])
                    before = jnp.concatenate(
                        [jnp.zeros((step, d), F32), terms[:-step]])
                    terms = terms + jnp.where(same[:, None], before, 0.0)
                    step *= 2
                per_token = jnp.bincount(tok, length=t + 1)[:t]
                last = jnp.cumsum(per_token) - 1
                return jnp.where(
                    (per_token > 0)[:, None],
                    jnp.take(terms, jnp.maximum(last, 0), axis=0), 0.0)
        if bound >= t * k:
            return wide()
        return jax.lax.cond(n_held <= bound, narrow, wide)

    def layer(shape, form):
        t, k, d, f, count, scored, score_fn, biased, n_group, topk_group = \
            shape[:10]
        tile = sm.held_row_tile(t * k, scored)
        ruled = sm.held_row_bound(t * k, count, scored, tile)
        forced = -(-sm.HELD_ROWS_MARGIN * t * k * count // scored // tile) * tile
        bound = {"wide": t * k, "narrow": min(ruled, forced)}.get(form, ruled)

        def fn(x, wg, bias, w_gate, w_up, w_down):
            def grouped(rows, sizes):
                def gg(lhs, rhs):
                    return grouped_gemm(lhs, rhs, sizes, tiling=(
                        tile, min(lhs.shape[1], 1024), min(rhs.shape[2], 1024)))
                with jax.named_scope("experts"):
                    return gg(jax.nn.silu(gg(rows, w_gate)) * gg(rows, w_up),
                              w_down)
            with jax.named_scope("route"):
                logits = jnp.dot(x, wg, preferred_element_type=F32)
                select_bias = bias if biased else None
                if form == "pr59":
                    gate_k, idx = pr59_route(logits, k, score_fn, select_bias,
                                             n_group, topk_group)
                else:
                    gate_k, idx = sm.route_topk(
                        logits, k, score_fn, select_bias, n_group=n_group,
                        topk_group=topk_group)
            if form == "pr59":
                return pr59_dispatch(x, gate_k, idx, count, grouped, bound)
            return sm.held_dispatch_gmm(x, gate_k, idx, 0, count, grouped,
                                        bound=bound)[0]
        fn.__name__ = f"held_layer_{form}"
        return fn, bound

    def by_scope(fn, inputs):
        """ms a call by scope label, most first, and their sum."""
        jitted = jax.jit(fn)
        telemetry.forget_programs()
        telemetry.keep_program(f"probe:{fn.__name__}", jitted.trace(*inputs))
        jax.block_until_ready(jitted(*inputs))
        with tempfile.TemporaryDirectory(prefix="held_layer_forms_") as logdir:
            with telemetry.trace_capture(logdir):
                for _ in range(reps):
                    out = jitted(*inputs)
                jax.block_until_ready(out)
            joined = join_logdir(logdir)[1]
        telemetry.forget_programs()
        if not joined["busy_s"]:
            return None, {}
        scopes = {s: round(1e3 * v / reps, 4)
                  for s, v in scope_tables(joined)["scope"]}
        return round(1e3 * joined["busy_s"] / reps, 4), scopes

    plans = [tuple(int(v) for v in p.split(",")) for p in
             args.plans.split(";") if p]
    own_plan = hc.combine_plan
    for name in names:
        shape = shapes[name]
        inputs = jax.jit(lambda key: operands(shape, key))(
            jax.random.PRNGKey(61))
        want = None
        for form in args.forms.split(","):
            t, k, d, count = shape[0], shape[1], shape[2], shape[4]
            for plan in [None] + (plans if form == "tree" else []):
                jax.clear_caches()                  # a fresh trace a plan
                fn, bound = layer(shape, form)
                hc.combine_plan = own_plan if plan is None else (
                    lambda t_, b_, c_, d_, i_, plan=plan:
                    plan if len(plan) == 3 else plan + (own_plan(
                        t_, b_, c_, d_, i_)[2],))
                line = dict(shape=name, form=form, bound=bound, rows=t * k,
                            plan=list(plan or own_plan(t, bound, count, d, 2))
                            if bound < t * k else None)
                try:
                    got = jax.jit(fn)(*inputs)
                except Exception as e:      # a plan the compiler refuses
                    say(**line, error=str(e)[:300])
                    continue
                if want is None:
                    want = got
                err = float(jnp.max(jnp.abs(got - want))
                            / jnp.maximum(jnp.max(jnp.abs(want)), 1e-9))
                ms, scopes = by_scope(fn, inputs)
                say(**line, rel_err_to_first=err, ms=ms, scopes=scopes)
            hc.combine_plan = own_plan
    return 0


if __name__ == "__main__":
    sys.exit(main())
