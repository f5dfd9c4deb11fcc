SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= to_date('1993-01-01')
  AND l_shipdate < to_date('1994-01-01')
  AND l_discount BETWEEN 0.01 AND 0.03
  AND l_quantity < 25
