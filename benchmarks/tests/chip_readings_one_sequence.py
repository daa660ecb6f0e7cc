"""``chip_readings.py`` for a cell whose batch is one sequence and whose
state fills most of the chip. Same arguments, same output. Two things differ:

* the ``half_batch`` fault there repeats the first half of the rows, and one
  row has no half: here the second half of every sequence repeats the first,
  inputs and targets alike, so the program trains on half of the tokens the
  reference sees;
* a control reading there makes the program's whole state beside the
  reference and uses its weights alone, which does not fit here: where only
  controls are asked for (``--control-bf16`` and no other kind), the state is
  not made. Take the controls in a call of their own.
"""

import sys

import numpy as np

import chip_readings
import run as bench


def repeat_first_half_of_each_sequence(batch):
    return tuple(np.concatenate([a[:, : a.shape[1] // 2]] * 2, axis=1) for a in batch)


def weights_alone(program, seed):
    weights = bench.load_module(bench.HERE, "weights.py")
    return None, program.make_weights(weights.seed_scalar(seed))


if __name__ == "__main__":
    chip_readings.repeat_first_half = repeat_first_half_of_each_sequence
    kinds = {a for a in sys.argv[1:] if a.startswith("--") and a not in ("--allow-cpu", "--benchmark")}
    if kinds == {"--control-bf16"}:
        bench.Program.start = weights_alone
    chip_readings.main()
