#!/usr/bin/env python
"""What the eight v1 hybrid families LOWER to, and their parameter trees, at
`tests/unit/models/hybrid_families.py`'s toy sizes, on the CPU, from shapes
alone (nothing is compiled or run; about a minute): for each family

- `loss`: the plain forward with the loss,
- `prefill`: a prefill that takes the family's walk (its budget patched as
  the family's own test patches it),
- `decode`: a decode step,

as `jax.jit(...).lower(...).as_text()` (no debug locations), one sha256 a
text, one JSON line a family. A PR that MOVES code these families share
proves no program changed by running this on the parent and on the change
(`--root <git archive of the parent>`, `--out` keeps the texts) and comparing
them (`--against`: `same`, `reordered` where the instructions are the same
multiset less their numbering, else `DIFFERENT`, which fails):

    JAX_PLATFORMS=cpu python tools/hybrid_lowered.py --root /root/scratch/parent --out /root/scratch/old
    JAX_PLATFORMS=cpu python tools/hybrid_lowered.py --out /root/scratch/new --against /root/scratch/old

`--params <file>` writes the table `tests/unit/models/hybrid_param_trees.txt`
holds (`test_hybrid_seam.py` pins it): a family's sorted `path shape dtype`
lines. The cells' weights are drawn by PATH: a renamed parameter is another
draw, and generate-reason's speed follows the draw by 1.5%.
"""

import argparse
import hashlib
import json
import os
import re
import sys

FAMILIES = ("nemotron_h", "phi4flash", "ling_linear", "keye_sparse",
            "deepseek_sparse", "openpangu", "afmoe", "qwen3_next")
# (rows, prompt, cache slots, the budget under which the prompt is walked):
# each family's own walked-prefill test's
WALKED = {"nemotron_h": (4, 21, 64, 2 * 21), "phi4flash": (4, 13, 32, 2 * 13),
          "ling_linear": (4, 23, 128, 2 * 23), "keye_sparse": (3, 23, 64, 8),
          "deepseek_sparse": (3, 23, 64, 8), "openpangu": (3, 23, 64, 8),
          "afmoe": (3, 20, 64, 30), "qwen3_next": (3, 24, 64, 8)}


def toy_configs():
    """name -> the family's config at the tests' toy sizes."""
    import jax.numpy as jnp
    from perfbench.manifest import Manifest
    from tests.unit.models import hybrid_families as hf
    adapted = {"keye_sparse": hf.KEYE_SIZES,
               "deepseek_sparse": hf.DEEPSEEK_SIZES,
               "openpangu": hf.OPENPANGU_SIZES, "afmoe": hf.AFMOE_SIZES,
               "qwen3_next": hf.QWEN3_NEXT_SIZES}
    manifest = Manifest()
    return {"nemotron_h": hf.NEMOTRON_CFG, "phi4flash": hf.PHI4_CFG,
            "ling_linear": hf.LING_CFG,
            **{name: manifest.module("configs", name + "_adapter").model_config(
                sizes, dtype=jnp.float32, dispatch_impl="gmm")
               for name, sizes in adapted.items()}}


def abstract_params(model):
    """The parameter tree as shapes and dtypes, no array made."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.utils.partitioning import extract_params_and_specs
    return extract_params_and_specs(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))[0]


def tree_lines(params):
    import jax
    return sorted(
        f"{jax.tree_util.keystr(path)} {tuple(leaf.shape)} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(params))


def lowered_texts(name, cfg):
    """{program: text} of family `name`."""
    import importlib

    import jax
    import jax.numpy as jnp
    models = {n: importlib.import_module("deepspeed_tpu.models." + n)
              for n in FAMILIES}
    module = models[name]
    model = module.init_params_and_specs(cfg)[0]
    params = abstract_params(model)
    rows, prompt, slots, budget = WALKED[name]
    ids = lambda s: jax.ShapeDtypeStruct((rows, s), jnp.int32)  # noqa: E731
    cache = jax.eval_shape(
        lambda: model.make_cache(rows, slots, dtype=jnp.float32))

    def serve(p, i, c):
        return model.apply({"params": p}, i, cache=c, mutable=["counters"])
    loss_fn = getattr(module, name + "_loss_fn")(model)
    texts = {"loss": jax.jit(loss_fn).lower(
        params, {"input_ids": ids(prompt)}, None).as_text()}
    # the budget is a constant of a module, read when the call is traced
    # (before ISSUE 62 DeepSeek's and openPangu's chunk was Keye's)
    patched = [(m, const, getattr(m, const)) for m in models.values()
               for const in ("PREFILL_TOKENS", "PREFILL_CHUNK")
               if hasattr(m, const)]
    for m, const, _ in patched:
        setattr(m, const, budget)
    try:
        texts["prefill"] = jax.jit(serve).lower(
            params, ids(prompt), cache).as_text()
    finally:
        for m, const, real in patched:
            setattr(m, const, real)
    texts["decode"] = jax.jit(serve).lower(params, ids(1), cache).as_text()
    return texts, tree_lines(params)


def verdict(text: str, other: str) -> str:
    """`same`; `reordered`: the same instructions less the numbers of their
    values, in another order; else `DIFFERENT`."""
    if text == other:
        return "same"
    bare = lambda t: sorted(re.sub(  # noqa: E731
        r"%(\w+?_)?\d+(#\d+)?|%arg\d+", "%", line) for line in t.splitlines())
    return "reordered" if bare(text) == bare(other) else "DIFFERENT"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the tree whose code is lowered")
    ap.add_argument("--out", help="a directory for the texts")
    ap.add_argument("--params", help="a file for the parameter trees' table")
    ap.add_argument("--against", help="another run's --out, to compare with")
    ap.add_argument("--families", default=",".join(FAMILIES))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    cfgs, table, different = toy_configs(), [], False
    for name in args.families.split(","):
        texts, lines = lowered_texts(name, cfgs[name])
        table += [f"[{name}]"] + lines
        against = {}
        if args.against:
            for program, text in texts.items():
                with open(os.path.join(args.against,
                                       f"{name}.{program}.txt")) as f:
                    against[program + "_against"] = verdict(text, f.read())
            different |= "DIFFERENT" in against.values()
        if args.out:
            for program, text in texts.items():
                with open(os.path.join(args.out, f"{name}.{program}.txt"),
                          "w") as f:
                    f.write(text)
        print(json.dumps({"family": name, "params": len(lines), **{
            program: hashlib.sha256(text.encode()).hexdigest()[:16]
            for program, text in texts.items()}, **against}), flush=True)
    if args.params:
        with open(args.params, "w") as f:
            f.write("\n".join(table) + "\n")
    return int(different)


if __name__ == "__main__":
    sys.exit(main())
