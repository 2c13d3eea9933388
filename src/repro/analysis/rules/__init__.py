"""Rule registry for repro-lint.  Each rule module exposes ``RULES``
(the rule-id strings it can emit) and ``check(files) -> list[Finding]``."""
from . import (
    jax_under_lock,
    obs_hot_path,
    pallas_trace,
    phase_transitions,
    sole_writer,
    tune_lookup,
)

ALL_RULE_MODULES = [jax_under_lock, sole_writer, phase_transitions,
                    pallas_trace, obs_hot_path, tune_lookup]

ALL_RULE_IDS = [rid for mod in ALL_RULE_MODULES for rid in mod.RULES]
