"""The benchmark's own code: generators, reference, trace reduction and
the serving driver.  Nothing here is imported by the program."""
