"""The least HBM bytes a training step's hyper-connections must move
(``kernels.hc_roofline``), from shapes alone.

A widened residual stream holds ``n`` streams of ``C`` lanes a token
(``hc_mult``, ``hidden_size``); every sublayer (two a layer) reads it
through the coefficients and the pre mix and writes it through the post
mix.  What any implementation must move, per sublayer and token, in
elements of the compute dtype:

- forward: the coefficients and the pre mix read ``X`` (``n C``) once
  (one pass can do both: a token's coefficients need its own row only)
  and write the sublayer's input ``u`` (``C``); the post mix, which needs
  the sublayer's result, reads ``X`` again and ``y`` (``C``) and writes
  ``X'`` (``n C``): ``3 n C + 2 C``;
- backward: the post mix's gradients of ``H_res`` and ``h_post`` read
  ``dX'``, ``X`` and ``y`` and hand the sublayer ``dy`` (``2 n C + 2 C``);
  after the sublayer's backward pass, the pre mix's and the
  coefficients' read ``du``, ``X`` and ``dX'`` and write ``dX`` (``3 n C +
  C``): ``5 n C + 3 C``;
- each sublayer's ``phi`` (``n C x (2n + n^2)``) read in both passes and
  its gradient written once.

The coefficients themselves (``2n + n^2`` a token) and the recomputation
of the forward pass are left out: a fused implementation keeps the first
on the chip, and one that keeps its activations needs no second forward.
Counting each stream once an operation is what keeps a faster, fused
implementation under 100 %.
"""

# the scopes of ``ops/llm.py``'s hyper-connection operators
SCOPES = ("hc.coef", "hc.mix")

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def itemsize(dtype):
    return _ITEMSIZE[str(dtype)]


def stream_elements_per_token(arch):
    """Elements one sublayer's hyper-connection must move for one token,
    forward and backward (module docstring)."""
    n, c = arch["hc_mult"], arch["hidden_size"]
    return (3 * n + 2) * c + (5 * n + 3) * c


def least_bytes(arch, rows, seq, size=2):
    """The least bytes of one training step's hyper-connections: ``rows x
    seq`` tokens through ``2 x num_hidden_layers`` sublayers, ``size``
    bytes an element."""
    n, c = arch["hc_mult"], arch["hidden_size"]
    sublayers = 2 * arch["num_hidden_layers"]
    weights = 3 * n * c * (2 * n + n * n)
    return sublayers * size * (rows * seq * stream_elements_per_token(arch)
                               + weights)
