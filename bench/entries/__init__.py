"""One driver per channel entry point, found by the traffic mix's
``entry`` name."""
