"""The seam between the eight v1 hybrid families and what they share
(`models/hybrid.py`, ISSUE 62), and the parameter trees a benchmark cell's
weights are drawn into.

The seam: a decision several families must know (how a prefill is cut, what
a serving pass hands the head, how a held share of experts is built) is
written once, and no family's file imports another's. The trees: the cells
draw their weights by PATH, and generate-reason's speed follows the draw by
1.5% against a bound of 1% (ROADMAP C8): a PR that renames, reshapes or
retypes a parameter learns it here, from shapes alone, not from a cell that
moved. `hybrid_param_trees.txt` was written at PR 61's tree
(`tools/hybrid_lowered.py --params`); a PR that MEANS to change a tree
writes it anew and says so.
"""

import ast
import functools
import importlib.util
import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
MODELS = os.path.join(REPO, "deepspeed_tpu", "models")
# what exists ONCE under `deepspeed_tpu/models/`, under whichever spelling
SHARED = ("RowGroups", "Chunks", "prefill_walk", "prefill_chunks", "embedded",
          "delta_chunked", "neumann_inverse", "dividing_chunk",
          "held_experts", "experts", "DenseFFN", "causal_lm")


def _tool():
    """`tools/hybrid_lowered.py`, which made the table (it imports no jax
    until it is called)."""
    spec = importlib.util.spec_from_file_location(
        "hybrid_lowered", os.path.join(REPO, "tools", "hybrid_lowered.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TOOL = _tool()
FAMILIES = TOOL.FAMILIES
toy_configs = functools.cache(TOOL.toy_configs)


def _models_imported(tree):
    """The modules of `deepspeed_tpu.models` a file imports, anywhere in it."""
    prefix = "deepspeed_tpu.models"
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == prefix:
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(prefix + "."):
            yield node.module[len(prefix) + 1:].split(".")[0]
        elif isinstance(node, ast.Import):
            yield from (alias.name[len(prefix) + 1:].split(".")[0]
                        for alias in node.names
                        if alias.name.startswith(prefix + "."))


def test_no_family_imports_a_sibling_and_what_they_share_is_defined_once():
    trees = {name[:-3]: ast.parse(open(os.path.join(MODELS, name)).read())
             for name in sorted(os.listdir(MODELS)) if name.endswith(".py")}
    for name in FAMILIES:
        reached = set(_models_imported(trees[name]))
        assert reached <= {"llama", "common", "latent", "hybrid"}, (
            name, sorted(reached))
    defined = {name: {node.name.lstrip("_") for node in tree.body
                      if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
               for name, tree in trees.items()}
    for shared in SHARED:
        homes = [name for name, names in defined.items() if shared in names]
        assert len(homes) <= 1, (shared, homes)
    assert {"RowGroups", "Chunks", "prefill_walk", "embedded", "held_experts",
            "causal_lm"} <= defined["hybrid"]


@pytest.mark.parametrize("name", FAMILIES)
def test_the_parameter_tree_is_the_one_the_cells_weights_are_drawn_into(name):
    """Sorted `path shape dtype` lines at `hybrid_families.py`'s toy sizes,
    from `jax.eval_shape` alone, against the table made at PR 61."""
    with open(os.path.join(os.path.dirname(__file__),
                           "hybrid_param_trees.txt")) as f:
        sections = re.split(r"^\[(\w+)\]\n", f.read(), flags=re.M)
    want = sections[sections.index(name) + 1].splitlines()
    module = importlib.import_module("deepspeed_tpu.models." + name)
    got = TOOL.tree_lines(TOOL.abstract_params(
        module.init_params_and_specs(toy_configs()[name])[0]))
    differ = sorted(set(got) ^ set(want))
    assert got == want, "\n".join(
        ("+ " if line in got else "- ") + line for line in differ)
