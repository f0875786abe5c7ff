"""The state-space LM's cell at toy size for the CPU tests: the toy model
of tests/lm_ssm_toy.py as a configuration file's dict, and a traffic mix
of a few short sessions with the same keys as
sessions128-ctx256-4k-sum180k.json."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lm_ssm_toy import TOY  # noqa: E402

CELL = "falcon-h1-pp12-decode-chat128-ctx256-4k"

CONFIG = dict(TOY, pipeline="lm", job="serve_ssm", assumed={})

TRAFFIC = {
    "kind": "serve", "loop": "closed", "sessions": 6,
    "ctx_min": 3, "ctx_max": 40, "ctx_sum": 90, "ctx_sum_tolerance": 0.5,
    "prefill_chunk": 8, "bucket_edges": [1, 4, 8], "max_batch": 6,
    "queue_capacity": 12, "deadline_s": 30.0,
    "prefill_deadline_s": 600.0, "wedge_timeout_s": 600.0,
    "page": 4, "cache_tokens": 2400, "max_len": 320,
    "trace_after_steps": 2, "trace_steps": 2,
    "check_sessions": 4, "check_steps": 3, "check_steps_below": 6,
    "check_short_below": 6, "check_long_above": 20,
    "check_mid_below": 16,
    # the controls' ``truncate`` at the toy's scale: the last 4 positions
    "control_truncate": 4,
    "reference_blocks": {"q_block": 8, "head_group": 5, "key_round": 16,
                         "mlp_block": 16, "pad_to": 64},
    # float32 on both sides: round-off of two orders of summation
    "limits": {"logits_rel_rms": 1e-4, "logits_max_gap": 1e-3,
               "state_rel_rms": 1e-4, "state_layer0_worst_head": 1e-4},
}
