"""A run whose timed path is broken underneath comes out NOT correct.

Each case drives ``run.py``'s ``main`` on the CPU (the rehearsal: the
harness's look for a chip is skipped, the rest of a run is the chip's) in a
process of its own, with one fault planted in the program under it, and
reads ``correct`` false and the number that failed beside its limit. The
same process without the fault is ``test_benchmark_grid.py``'s rehearsal of
the cell, which reads true.

Like its neighbours, this module loads no TPU library while it is imported.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a served answer altered where the engine completes it: one token in the
# middle becomes its neighbour in the vocabulary
TOKEN_ALTERED = '''
import numpy as np
from paddle_tpu.serving.request import Response
complete = Response._complete
def altered(self, outputs=None, error=None):
    if outputs is not None and len(outputs.get("tokens", ())) > 2:
        tokens = np.array(outputs["tokens"])
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 64
        outputs = dict(outputs, tokens=tokens)
    return complete(self, outputs, error)
Response._complete = altered
'''

# a step that returns its state unchanged: the first step's loss again, and
# no optimizer step behind it
STATE_UNCHANGED = '''
from benchmark.builders._program import ProgramTrainer
step = ProgramTrainer.step
def frozen(self):
    if not hasattr(self, "_first_loss"):
        self._first_loss = step(self)
    return self._first_loss
ProgramTrainer.step = frozen
'''

FAULTS = [
    ("a served token altered", TOKEN_ALTERED, "decoder_1024x24.chat_steady",
     ["worst_token_sigma_behind", "checked_answers_wrong"]),
    ("a step that leaves its state unchanged", STATE_UNCHANGED,
     "resnet50.train_b128", ["last_loss_below_first"]),
]


@pytest.mark.parametrize("what, fault, cell, fails", FAULTS,
                         ids=[f[0] for f in FAULTS])
def test_a_fault_under_the_timed_path_reads_not_correct(what, fault, cell,
                                                        fails):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
            "from benchmark import run\n" + fault +
            f"run.main(['--workload', {cell!r}, '--seed', '3500000077', "
            "'--seconds', '1', '--trace', '0', '--rehearse-cpu'])\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert [name for name, n in line["compared"].items()
            if not n["holds"]] == fails
    for name in fails:
        assert f"compared {name}: " in p.stderr
    assert "FAILS" in p.stderr.strip().splitlines()[-len(
        line["compared"]) + list(line["compared"]).index(fails[0])]
