"""tpucomms — the compiled (post-SPMD) static-analysis layer.

tpulint checks Python spellings, tpuverify checks traced programs
(jaxprs + AOT lowerings); tpucomms checks what GSPMD actually *inserted*
at compile time: it parses ``compiled.as_text()`` of every program in
the engine matrix for collective ops, decodes their ``replica_groups``
back to canonical mesh axes, and enforces the communication contracts
the paper's ZeRO schedule is defined by (docs/static_analysis.md,
compiled layer).

Import surface mirrors the siblings: the heavy builders live in
``put.py`` and import jax lazily; ``hlo.py`` is stdlib-only.
"""

from deepspeed_tpu.tools.tpucomms.core import (  # noqa: F401
    BASELINE_NAME,
    Contract,
    Violation,
    all_contracts,
    load_baseline,
    new_violations,
    register,
    save_baseline,
    verify,
)
