SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= to_date('1996-01-01')
  AND l_shipdate < to_date('1997-01-01')
  AND l_discount BETWEEN 0.03 AND 0.05
  AND l_quantity < 25
