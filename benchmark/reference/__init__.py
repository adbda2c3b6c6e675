"""The plain reference: native queries in numpy over the generator's raw columns."""
