"""The stage vocabulary: one name per stage of the compiled step.

The program wraps each stage in ``jax.named_scope(<name>)`` where the work
is written, so every HLO instruction it lowers to carries the name in its
``op_name`` metadata (``jit(step)/pointwise.cross/...``), and a device
trace can put each op's time on a stage. Scopes are metadata only: the
compiled program is the same with them as without.

Every name starts with its layer (``fft``, ``fold``, ``relayout``,
``pointwise``), the part before the first dot. An op's stage is the
innermost stage scope of its ``op_name``. This module holds names only
and stays importable without jax, like the rest of ``repro.obs``.
"""

from __future__ import annotations

# 1D-FFT passes: the scope sits outside ``kops.fft1d``/``rfft1d``/``irfft1d``
FFT_X = "fft.x"
FFT_Y = "fft.y"
FFT_Z = "fft.z"

# fold exchanges: the collective or the RDMA kernel that moves the blocks
FOLD_EXCHANGE = "fold.exchange"

# local stage, relayouts: data movement with no arithmetic
RELAYOUT_FOLD_PACK = "relayout.fold_pack"        # stack_blocks
RELAYOUT_FOLD_UNPACK = "relayout.fold_unpack"    # merge_blocks
RELAYOUT_PERMUTE = "relayout.permute"            # pencil transposes
RELAYOUT_R2C_PAD = "relayout.r2c_pad"            # kx padding after rfft
RELAYOUT_R2C_CROP = "relayout.r2c_crop"          # N/2+1 slices
RELAYOUT_R2C_UNTANGLE = "relayout.r2c_untangle"  # rfft's paired lines' spectra
RELAYOUT_C2R_HERMITIAN = "relayout.c2r_hermitian"  # irfft's upper half
#: not a scope: the trace reduction's name for XLA's layout copy of a
#: program argument (an op whose producers lead back to a parameter)
RELAYOUT_ENTRY = "relayout.entry"

# local stage, pointwise: arithmetic on the spectrum or the physical field
POINTWISE_CURL = "pointwise.curl"
POINTWISE_CROSS = "pointwise.cross"
POINTWISE_DEALIAS = "pointwise.dealias"
POINTWISE_PROJECT = "pointwise.project"
POINTWISE_IFRK4_FACTORS = "pointwise.ifrk4_factors"
POINTWISE_IFRK4_COMBINE = "pointwise.ifrk4_combine"
POINTWISE_DIAGONAL = "pointwise.diagonal"

#: every scope the program puts on its work
SCOPES = (FFT_X, FFT_Y, FFT_Z, FOLD_EXCHANGE,
          RELAYOUT_FOLD_PACK, RELAYOUT_FOLD_UNPACK, RELAYOUT_PERMUTE,
          RELAYOUT_R2C_PAD, RELAYOUT_R2C_CROP, RELAYOUT_R2C_UNTANGLE,
          RELAYOUT_C2R_HERMITIAN,
          POINTWISE_CURL, POINTWISE_CROSS, POINTWISE_DEALIAS,
          POINTWISE_PROJECT, POINTWISE_IFRK4_FACTORS,
          POINTWISE_IFRK4_COMBINE, POINTWISE_DIAGONAL)
