"""The benchmark's own machinery: everything a number is computed with
lives here or beside it under benchmark/, never in the program."""
