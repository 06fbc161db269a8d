"""The second family: `data/second_family/` holds a whole benchmark of another
family (a toy sparse-expert decoder: its configuration, `counts`, adapter over
`deepspeed_tpu.models.mixtral`, plain reference, a traffic mix and a
`BENCHMARK.json`) and no file the benchmark has. Tests run it from a copy in a
temporary directory, where they may also break it."""

import json
import os
import shutil

import pytest

SECOND_FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "second_family")


@pytest.fixture
def second_family(tmp_path):
    """`edit(doc, sizes)` changes the copy's `BENCHMARK.json` and its one
    configuration file in place; the call returns the manifest's path."""
    root = tmp_path / "second_family"
    shutil.copytree(SECOND_FAMILY, root,
                    ignore=shutil.ignore_patterns("__pycache__"))

    def build(edit=None):
        bench = root / "BENCHMARK.json"
        doc = json.loads(bench.read_text())
        conf = root / doc["configs"][0]["file"]
        sizes = json.loads(conf.read_text())
        if edit is not None:
            edit(doc, sizes)
        bench.write_text(json.dumps(doc))
        conf.write_text(json.dumps(sizes))
        return str(bench)
    return build
