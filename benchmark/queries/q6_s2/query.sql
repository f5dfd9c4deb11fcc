SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= to_date('1995-01-01')
  AND l_shipdate < to_date('1996-01-01')
  AND l_discount BETWEEN 0.08 AND 0.10
  AND l_quantity < 24
