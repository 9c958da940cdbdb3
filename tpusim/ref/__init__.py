"""Plain references: straightforward implementations of the program's
semantics that share none of its kernels (tests and the benchmark compare
against them)."""
