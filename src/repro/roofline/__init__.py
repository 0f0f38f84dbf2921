from .analysis import (PEAKS, Peaks, RooflineTerms,  # noqa: F401
                       analyze_compiled, peaks_for)
