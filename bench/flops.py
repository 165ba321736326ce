"""Model FLOPs of one training step. The configuration's reference module
(``bench/reference/<module>.py``, named by the file's ``"reference"``)
counts them, ``flops_per_token(config, seq_len)``, by one rule for every
module: the operations that the forward and backward passes require for
the configuration as it is run (its widths, and the experts and vocabulary
held here), 6 FLOPs per token for each matmul parameter a token passes
through (2 forward, 4 backward), the LM head included and the embedding
lookup excluded, plus the score and value products of attention.
Recomputation (remat) and the masked half of a causal score matrix that an
implementation may compute are not counted: this is the work the step
requires, not the work a program happens to do.
"""
from __future__ import annotations


def flops_per_step(cfg: dict, global_batch: int, seq_len: int) -> float:
    from bench.reference import for_config
    return (for_config(cfg).flops_per_token(cfg, seq_len)
            * global_batch * seq_len)
