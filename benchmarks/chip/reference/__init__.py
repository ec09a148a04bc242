"""Plain references the benchmark judges the program's answers by.

numpy only: nothing here imports the program or takes what it made.
``bf16`` rounds to bfloat16 and back; the controls pass it as ``q``
to compute a reference one precision below the configuration's float32.
"""
import ml_dtypes
import numpy as np


def bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)
