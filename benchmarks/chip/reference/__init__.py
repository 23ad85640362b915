"""Plain references: the configuration's tables and straightforward
implementations of the same semantics as the program, importing nothing of
it."""
