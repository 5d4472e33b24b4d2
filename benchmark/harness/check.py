"""The comparison that decides ``correct``: the system against the
configuration's plain reference, outside the timed window.

Tolerances are relative to the largest reference value of the compared
tensor.  They are not constants: a deep network with train-mode batch norm
at random weights amplifies rounding by several orders of magnitude (two
float32 evaluations of ResNet-50's gradients differ by 1-4 % in the early
layers, both equally far from a float64 evaluation).  So the reference is
evaluated twice, the second time with every weight and input moved by one
float32 ulp, and a quantity's tolerance is ``noise_factor`` times how far
that moved it, with a floor.  A system that computes in a lower precision
than float32 (bfloat16's ulp is 65,536 times larger), or that drops a term
larger than the rounding noise, lands far outside.
"""

import numpy as np


def relative_error(got, want):
    """max |got - want| over max |want| (1.0 where both are all zero)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    scale = float(np.abs(want).max()) if want.size else 0.0
    diff = float(np.abs(got - want).max()) if want.size else 0.0
    if not np.isfinite(diff):
        return float("inf")
    return diff / scale if scale > 0 else (0.0 if diff == 0 else 1.0)


def one_ulp(array, rng):
    """``array`` with every element moved by one float32 ulp, up or down."""
    a = np.asarray(array, np.float32)
    signs = rng.choice(np.float32([-1.0, 1.0]), size=a.shape)
    return (a * (np.float32(1.0) + np.float32(2.0 ** -23) * signs)) \
        .astype(np.float32)


def against_reference(reference, config, system, say):
    """``system``: what the entry took from the program on the check batch:
    {"params": [(name, value)] in the program's order, "x", "y",
     "dropout_masks", "logits", "loss", "gradients": {name: value}}.
    -> True when logits, loss and the named gradients agree."""
    arch = config["architecture"]
    tol = config["tolerances"]
    names = config["check_gradients"]
    missing = [n for n in names if n not in system["gradients"]]
    if missing:
        say("check: the system gave no gradient for %s" % missing)
        return False
    params = [(n, np.asarray(v, np.float32)) for n, v in system["params"]]
    x, y = np.asarray(system["x"], np.float32), np.asarray(system["y"])
    masks = [np.asarray(m, np.float32) for m in system["dropout_masks"]]
    rng = np.random.RandomState(0)
    ref, moved = reference.outputs(
        arch, [(params, x),
               ([(n, one_ulp(v, rng)) for n, v in params], one_ulp(x, rng))],
        y, masks)

    rows = [("logits", system["logits"], ref[0], moved[0]),
            ("loss", system["loss"], ref[1], moved[1])]
    rows += [("gradients", system["gradients"][n], ref[2][n], moved[2][n],
              n) for n in names]
    ok = True
    for row in rows:
        kind, got, want, want_moved = row[:4]
        label = row[4] if len(row) > 4 else kind
        err = relative_error(got, want)
        noise = relative_error(want_moved, want)
        limit = max(tol["floor"][kind], tol["noise_factor"] * noise)
        passed = err <= limit
        ok = ok and passed
        say("check %-28s err %.3g  limit %.3g (one-ulp response %.3g)  %s"
            % (label, err, limit, noise, "ok" if passed else "FAIL"))
    return ok
