"""repro.apps -- spectral applications on top of the FFT plan front-end.

Each solver takes a :class:`repro.core.Plan`, so every choice the plan
layer offers -- collective backend (pinned / cost-model auto / measured),
slab vs pencil decomposition, r2c vs c2c transforms, calibrated comm
params -- flows through the application unchanged. The apps never look
at the mesh directly: they read the plan's
:meth:`~repro.core.Plan.spectral_axes` layout contract and operate in
whatever frequency-domain layout (transposed, reversed, Hermitian-padded)
the plan produces.

- :mod:`repro.apps.poisson` -- periodic FFT Poisson solver
- :mod:`repro.apps.convolve` -- distributed circular convolution/correlation
- :mod:`repro.apps.derivatives` -- spectral gradient / laplacian
- :mod:`repro.apps.spectral` -- shared wavenumber-grid plumbing
- :mod:`repro.apps.npb_ft` -- NAS Parallel Benchmarks FT (evolve, checksum)
"""

from repro.apps.convolve import fft_convolve, fft_correlate
from repro.apps.derivatives import gradient, laplacian
from repro.apps.npb_ft import NPBFT
from repro.apps.poisson import solve_poisson
from repro.apps.spectral import plan_directions, wavenumbers

__all__ = [
    "NPBFT",
    "fft_convolve",
    "fft_correlate",
    "gradient",
    "laplacian",
    "plan_directions",
    "solve_poisson",
    "wavenumbers",
]
