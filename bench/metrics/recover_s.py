"""Mean wall time of ``fail_server`` with eager batched recovery over the
window's recovery pass (transition, gather, one batched decode, install)."""
import numpy as np


def read(run):
    return None if not run.recover_s else float(np.mean(run.recover_s))
