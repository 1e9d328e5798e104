"""The accepted families are untouched by a change to what they share
(`engine/core.py`, `ops/moe.py`, `models/base.py`, and since PR 42
`models/smallthinker.py::TwoPools`): the step programs of the toy
configurations of tests/test_named_scopes.py lower to the text they lowered
to before (text without locations, so an edit that only moves lines does
not show). The digests in tests/data/step_program_digests.json were taken by
this file's own `digests()`: the dense, `deepseek_v3` and `smallthinker`
ones at the parent of PR 40 (commit c004975), `nemotron_h`'s at the parent
of PR 42 (commit 1b02cb5), `afmoe`'s as PR 42 left its module; PR 44 took
the four expert families' again (it MEANT to change them: the way back of
`ops/moe.py`, `_back_to_tokens`; the dense family's stayed as they were);
PR 48 added one digest a family, the chunk call of the SMALLER bucket, which
`warmup()` always ran and the warm compile's own list had left out (the one
list of engine/step_programs.py has both's coverage); the six that were
there kept theirs; PR 51 added `lfm2_moe`'s as it left its module (the
other five kept theirs); PR 54 MEANT to change every family's two chunk
programs (digests 5 and 6: `prefill_chunk_fn` takes its per-request
arguments by row, so that a call can hold two prompts' pieces) and added
two, the same calls at two slots, in the families that share chunk calls
(core.pair_rows: dense, nemotron_h, lfm2_moe); the decode chunk's and the
four cold calls' stayed as they were:

    JAX_PLATFORMS=cpu python tests/test_step_programs_unchanged.py > tests/data/step_program_digests.json

A PR that MEANS to change one of these programs regenerates the file and
says so; one that does not has changed a program it shares without knowing.
"""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import test_named_scopes as scopes  # noqa: E402

FAMILIES = {
    "dense": None, "deepseek_v3": scopes.DEEPSEEK, "smallthinker": scopes.SMALLTHINKER, "nemotron_h": scopes.NEMOTRON_H,
    "afmoe": scopes.AFMOE, "lfm2_moe": scopes.LFM2_MOE,
}
DIGESTS = os.path.join(HERE, "data", "step_program_digests.json")


def digests(family: str) -> list[str]:
    """sha256 of every program the warm compile lowers for the family's toy
    configuration: the decode chunk, 2 buckets x 2 sizes, a chunk call a bucket, the same at two slots where the family shares calls."""
    return [hashlib.sha256(text.encode()).hexdigest() for text, _ in scopes._lowered_programs(True, FAMILIES[family])]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_accepted_families_lower_to_the_programs_they_lowered_to(family):
    with open(DIGESTS) as f:
        want = json.load(f)
    got = digests(family)
    assert len(got) == len(want[family]) >= 4
    changed = [i for i, (a, b) in enumerate(zip(got, want[family])) if a != b]
    assert not changed, f"{family}: step program(s) {changed} no longer lower to the text of {DIGESTS}"


if __name__ == "__main__":
    import conftest  # noqa: F401  (the tests' own devices and matmul precision)

    print(json.dumps({family: digests(family) for family in sorted(FAMILIES)}, indent=1))
