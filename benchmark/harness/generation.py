"""The default ``generation`` module: a family whose step gives each
sequence the tokens that FOLLOW its last one (one a step, or a verified
draft of the same law), each predicted from the served prefix alone.

It is what the serving runner's two comparisons were before they were a
role of the configuration; the bodies stay where the tests and the
builders' tools import them (``serve_runner``, ``served``) and exist
once. ``harness/__init__.py`` has the role's contract, and what a family
whose step commits several tokens of a block writes instead.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import serve_runner, served


def program_rows(model, eng_cfg: Dict[str, Any], ids, seed: int
                 ) -> Dict[str, Any]:
    """The family's prefill of the first ``REF_PROMPT_TOKENS`` of
    ``ids`` into a new cache of the kind, type and length the cell's
    engine holds, then the rest fed one token at a time through it:
    ``{"prefill": [V], "decode": [len(ids) - REF_PROMPT_TOKENS, V]}``."""
    n_prompt = serve_runner.REF_PROMPT_TOKENS
    prefill_row, state = serve_runner.prefill_into_cache(
        model, eng_cfg, ids[:n_prompt], seed)
    return {"prefill": prefill_row,
            "decode": serve_runner.decode_through_cache(state,
                                                        ids[n_prompt:])}


def reference_rows(reference, canonical, arch: Dict[str, Any],
                   quant: Dict[str, Any], ids) -> Dict[str, Any]:
    """The same rows from ONE causal pass of the reference over
    ``ids``."""
    return serve_runner.reference_logits(
        reference, canonical, arch, quant, ids,
        serve_runner.REF_PROMPT_TOKENS)


served_gaps = served.next_token_gaps
