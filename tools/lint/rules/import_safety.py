"""import-safety: no ray_tpu module initializes a JAX backend at import.

Plugin wrapper around tools/check_import_safety.py (the bogus-platform
canary subprocess — see that module for the mechanism: an import that
initializes a backend takes the chip from the process meant to own it).
Marked slow: it imports the whole package in a
child process, so CI surfaces that already run the canary directly
(tests/test_import_safety.py) invoke the linter with --skip-slow.
"""

from __future__ import annotations

from typing import Iterable, Sequence

from ..framework import Analyzer, FileContext, Finding, register

RULE = "import-safety"


@register
class ImportSafety(Analyzer):
    name = RULE
    per_file = False
    slow = True
    description = (
        "subprocess canary: importing every ray_tpu module under a bogus "
        "JAX_PLATFORMS must not initialize a backend (one process per chip)"
    )

    def check_tree(self, ctxs: Sequence[FileContext]) -> Iterable[Finding]:
        # Only meaningful against the whole package.
        if not any(c.path == "ray_tpu/__init__.py" for c in ctxs):
            return ()
        from tools import check_import_safety

        rc = check_import_safety.main()
        if rc != 0:
            return (Finding(
                rule=RULE,
                path="ray_tpu/__init__.py",
                line=1,
                message=(
                    f"import-safety canary failed (rc={rc}); run "
                    "`python tools/check_import_safety.py` for the module list"
                ),
                snippet="",
            ),)
        return ()
