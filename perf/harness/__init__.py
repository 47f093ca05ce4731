"""The yardstick: loader, clock arithmetic, peaks, trace reduction, the
result line. It names no model, no cell and no metric but ``setup_s``,
which the harness takes itself."""
