"""Scripts a builder runs by hand on the chip; no run of a cell uses them."""
