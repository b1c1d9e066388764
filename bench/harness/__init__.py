"""The chip benchmark's harness: spec loading, traffic, clocks, window
arithmetic, tracing, checks and the result line."""
