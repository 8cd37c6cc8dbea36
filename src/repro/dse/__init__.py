"""``repro.dse`` — the DSE command-line entry point package.

``python -m repro.dse`` runs :func:`repro.dse_cli.main`, which turns its
flags into one :class:`repro.dse_cli.SearchSpec` (the search problem,
checked once) and runs it.  The core algorithm lives in
``repro.core.dse`` (Algorithm 1) and the batched cost-table engine in
``repro.core.cost_table``; the scalar per-cell loop there is the tests'
oracle, not a CLI option.
"""

from repro.dse_cli import SearchSpec, main, run_dse

__all__ = ["SearchSpec", "main", "run_dse"]
